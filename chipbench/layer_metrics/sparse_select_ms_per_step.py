"""Device time of the sparse layers' SELECTION per traced step: every op
under the scope ``sparse.select`` (``ops/sparse_attention.py:
select_blocks`` as a ``sparse_attention`` layer calls it: the pooled
keys, the scores of every query head against them and their softmax,
the sum over a group's heads, the max-pool onto blocks, the top-k and
the table the kernels are handed), forward and, where a remat mode
re-runs it, recomputed; it has no backward (``chipbench/scopes.py``).
``None`` for a program without the scope (one from before it, or a model
with no such layer)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "sparse.select")
    except ValueError:       # a program from before the scope
        return None
