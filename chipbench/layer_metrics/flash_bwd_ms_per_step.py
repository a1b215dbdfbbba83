"""Device time of the two backward flash kernels per traced step, found
by name: Mosaic custom calls that carry ``hvd_flash_bwd`` (``_dq``,
``_dkv``) in their ``frontend_attributes={kernel_metadata={...}}``. On
the v5e an op's event is its HLO text without ``metadata=``, and with
the compile cache on (locations cut) the instruction is
``%tpu_custom_call.N``, so the kernel metadata is what tells the kernels
apart (read off a chip trace, PR 25); operands are not looked at. The
forward kernel is ``flash_ms_per_step`` minus this. ``None`` for a
program whose kernels carry no such name."""

import re

from chipbench import xplane

_IN_METADATA = re.compile(r"kernel_metadata=\{[^}]*hvd_flash_bwd")


def is_flash_bwd(ev):
    return (xplane.is_mosaic_call(ev)
            and _IN_METADATA.search(ev.name) is not None)


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    ns = chip.class_ns(is_flash_bwd)
    return ns / 1e6 / chip.steps if ns else None
