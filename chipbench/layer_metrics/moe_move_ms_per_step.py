"""Device time that moves the routed rows per traced step: the scopes
``hvd.moe.dispatch`` (sorts, group counts, row gathers, block writes and
their ``while`` / ``conditional``) and ``hvd.moe.combine`` (weighted
sums, selects, scatter-adds, zero-fills, the later chunks' joins), all
phases and WHATEVER the opcode (``chipbench/scopes.py``):
``moe_dispatch_ms_per_step`` guesses the same layer from outside, by
``sort`` and ``kind=kCustom``, and a pass that changes kind crosses its
edge. ``None`` for a program that has no scope tables or no expert
layer."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "moe.dispatch", "moe.combine")
