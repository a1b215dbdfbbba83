"""Device time of a ``mamba2`` layer's two projections per traced step:
every op under the scope ``ssd.proj`` (``in_proj`` to ``[z, X B C, dt]``
and ``out_proj``, with what the compiler folds into them), all phases
(``chipbench/scopes.py``). ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssd.proj")
    except ValueError:       # a program from before the scope
        return None
