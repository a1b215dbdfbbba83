"""Device time of the dense SwiGLU FFNs and the shared expert per traced
step: the scope ``hvd.ffn``, all phases (``chipbench/scopes.py``).
``None`` for a program that has no scope tables or no such layer."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "ffn")
