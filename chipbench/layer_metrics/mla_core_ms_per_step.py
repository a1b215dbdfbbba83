"""Device time of latent attention's core per traced step: every op
under the scope ``mla.core`` (``models/llama.py:_latent_attention``: the
assembly of ``q`` and ``k`` from their slices, the one rotated key
broadcast to the heads, the transposes into the kernels' layout and the
flash kernels at 192 / 128, ``hvd_flash_fwd`` and
``hvd_flash_bwd_fused`` by their ``kernel_metadata``, the backward's
``delta``), forward, again where a remat mode re-runs it, and backward
(``chipbench/scopes.py``). ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "mla.core")
    except ValueError:       # a program from before the scope
        return None
