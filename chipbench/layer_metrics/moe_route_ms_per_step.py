"""Device time of the routers per traced step: the scope
``hvd.moe.route`` (the router matmul, the scores, top-k, the pick and
normalisation of the chosen scores, the balance statistics and the aux
term), all phases (``chipbench/scopes.py``). ``None`` for a program
that has no scope tables or no expert layer."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "moe.route")
