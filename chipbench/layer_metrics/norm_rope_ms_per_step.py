"""Device time of the RMSNorms (per-head q/k-norm included) and the
rotary embedding per traced step: the scopes ``hvd.norm`` and
``hvd.attn.rope``, all phases (``chipbench/scopes.py``): the passes a
fused norm/RoPE kernel would take. A norm the compiler folds into a
matmul's prologue counts with the matmul (the run's ``mixed_pct`` says
how much time such fusions hold). ``None`` for a program that has no
scope tables."""

from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "norm", "attn.rope")
