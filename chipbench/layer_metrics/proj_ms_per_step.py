"""Device time of the token mixers' projections per traced step: the
scopes ``hvd.attn.proj`` (q, k, v, the output projection, the output
gate) and ``hvd.conv.proj`` (a conv layer's in- and out-projection),
all phases (``chipbench/scopes.py``). Whatever the compiler folds into
those matmuls' fusions counts with them: a fusion is read by what it
produces. ``None`` for a program that has no scope tables."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "attn.proj", "conv.proj")
