"""Device time of latent attention's projections per traced step: every
op under the scope ``mla.proj`` (the two down-projections, the RMSNorm
on each latent, the two up-projections a head and the output projection,
with their backward matmuls), all phases. What the compiler folds into
these matmuls counts here (``chipbench/scopes.py``). ``None`` for a
program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "mla.proj")
    except ValueError:       # a program from before the scope
        return None
