"""Device time of the two ends of the model per traced step: the scopes
``hvd.embed`` (the lookup and its scatter-add), ``hvd.head`` (the logits
matmul and its two gradients) and ``hvd.loss`` (logsumexp, the pick of
the target logit, the mean), all phases (``chipbench/scopes.py``).
``None`` for a program that has no scope tables."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "embed", "head", "loss")
