"""Device time of the expert layers' grouped GEMMs (megablox ``gmm`` and
``tgmm``: three forward, three ``dlhs``, three ``tgmm`` a layer, and
whatever the remat mode runs a second time) per traced step.

What it matches: Mosaic custom calls whose event text does NOT hold
``hvd_flash``. jax 0.9.0's megablox hands its ``pallas_call``s no
``metadata=``, and on this chip an op's event carries no other name
(PR 25), so the grouped GEMMs are the Mosaic calls the flash kernels'
``kernel_metadata`` does not claim. What would break it: a third kind of
Mosaic kernel in the program without a name of its own (it would be
counted here), or the flash kernels losing theirs (they would be).
``None`` for a program with no such call."""

from chipbench import xplane


def is_grouped_gemm(ev):
    return xplane.is_mosaic_call(ev) and "hvd_flash" not in ev.name


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    ns = chip.class_ns(is_grouped_gemm)
    return ns / 1e6 / chip.steps if ns else None
