"""Device time of the flash calls that carry a WINDOW per traced step:
Mosaic custom calls whose ``kernel_metadata`` names an ``hvd_flash``
kernel and holds a ``"window"`` entry
(``{"kernel":"hvd_flash_fwd","window":"2048"}``; a call without a window
has the name alone, ``ops/flash_attention.py:_pallas_dispatch``).
Forward, backward and whatever a remat mode runs a second time. The full
layers' calls are ``flash_bwd_ms_per_step`` and the Mosaic total less
these. ``None`` for a program whose flash calls carry no window (every
program before the band existed)."""

import re

from chipbench import xplane

_WINDOWED = re.compile(
    r"kernel_metadata=\{[^}]*hvd_flash[^}]*\"window\"")


def is_windowed_flash(ev):
    return (xplane.is_mosaic_call(ev)
            and _WINDOWED.search(ev.name) is not None)


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    ns = chip.class_ns(is_windowed_flash)
    return ns / 1e6 / chip.steps if ns else None
