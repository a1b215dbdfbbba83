"""Device time of the latent projections round the routed experts per
traced step: every op under the scope ``moe.latent`` (an expert layer's
``moe_lat_down`` [D, l] in front of the dispatch and ``moe_lat_up`` [l,
D] behind the combine), all phases (``chipbench/scopes.py``). ``None``
for a program without the scope (one from before it, or a model whose
experts work at the model's width)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "moe.latent")
    except ValueError:       # a program from before the scope
        return None
