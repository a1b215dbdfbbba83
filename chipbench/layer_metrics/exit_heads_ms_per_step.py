"""Device time of a looped decoder's objective per traced step: the
scopes ``hvd.head`` (the logits of every exit and their two gradients),
``hvd.loss`` (logsumexp, the pick of the target logit, the mean) and
``hvd.exit`` (the gate's projection, the exit distribution, the entropy,
the weighting of the R losses), all phases (``chipbench/scopes.py``).
``None`` for a program that has no scope tables or no exits."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "head", "loss", "exit")
    except ValueError:       # a program from before the exits
        return None
