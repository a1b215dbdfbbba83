"""Device time of what stands between a lightning layer's projections
and its recurrence per traced step: every op under the scope
``lightning.chain`` (the q/k RMSNorm a head, RoPE, the ``1 / sqrt(d)``
on ``q``, and behind the recurrence the RMSNorm over the concatenated
heads and the output gate's sigmoid), all phases
(``chipbench/scopes.py``). What the compiler folds into the projections'
matmuls counts with those. ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "lightning.chain")
    except ValueError:       # a program from before the scope
        return None
