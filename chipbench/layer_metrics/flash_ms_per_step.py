"""Device time of every Mosaic custom call on the op line per traced
step: in a dense decoder's train step only the flash forward, backward
and recomputed forward are such. Matched by the kind of op, not by a
name a later PR may change."""

from chipbench import xplane


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    ns = chip.class_ns(xplane.is_mosaic_call)
    return ns / 1e6 / chip.steps if ns else None
