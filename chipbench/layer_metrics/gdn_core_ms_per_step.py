"""Device time of the gated delta rule per traced step: every op under
the scope ``gdn.core`` (``ops/gated_delta_rule.py`` as a
``linear_attention`` layer calls it: the decays, the in-chunk scores,
the triangular systems of the WY form, the state-carrying scan over the
chunks and, in the backward pass, its reverse scan), forward, again
where a remat mode re-runs the layer, and backward
(``chipbench/scopes.py``). An op the compiler fuses with a neighbour of
another scope is read by what the fusion is built round. ``None`` for a
program without the scope (one from before it, or a model with no such
layer)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "gdn.core")
    except ValueError:       # a program from before the scope
        return None
