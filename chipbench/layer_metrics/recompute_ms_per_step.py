"""Device time a traced step spends running the forward pass a SECOND
time: every op whose phase is ``recomputed``, whatever its scope, i.e.
what ``jax.checkpoint`` re-emits under ``rematted_computation``
(``chipbench/scopes.py``; docs/metrics.md "Device scopes"). The price of
the cell's remat mode, which no opcode tells from the first forward.
``None`` for a program that has no scope tables or recomputes nothing."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, phase="recomputed")
