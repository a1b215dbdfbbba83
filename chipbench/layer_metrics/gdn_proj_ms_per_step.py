"""Device time of a ``linear_attention`` layer's projections per traced
step: every op under the scope ``gdn.proj`` (the two in-projections,
``[q, k, v, z]`` and ``[b, a]``, and the output projection), all phases
(``chipbench/scopes.py``). Whatever the compiler folds into those
matmuls' fusions counts with them. ``None`` for a program without the
scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "gdn.proj")
    except ValueError:       # a program from before the scope
        return None
