"""Device time of a ``mamba`` layer's four projections per traced step:
every op under the scope ``ssm.proj`` (``W_in`` to ``[u, z]``, ``W_x``
to the step size's rank, ``B`` and ``C``, ``W_dt`` to the step size,
``W_out``), all phases (``chipbench/scopes.py``); an elementwise
neighbour the compiler folds into one of these matmuls counts here.
``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssm.proj")
    except ValueError:       # a program from before the scope
        return None
