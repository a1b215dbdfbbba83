"""Device time of the attention over the chosen blocks per traced step:
every op under the scope ``sparse.core`` (``ops/sparse_attention.py:
sparse_attention``: the two kernels, ``hvd_sparse_attn_fwd`` and
``hvd_sparse_attn_bwd`` by their ``kernel_metadata``, the copies that
lay a token's heads side by side down a tile's rows and back, the
backward's ``delta`` and the casts of ``dk`` / ``dv``), forward, again
where a remat mode re-runs the layer, and backward
(``chipbench/scopes.py``). ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "sparse.core")
    except ValueError:       # a program from before the scope
        return None
