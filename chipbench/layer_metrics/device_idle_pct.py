"""Device idle share of the traced steps: 1 - union of op intervals on
the chip's op line / the window of whole steps, in percent."""


def read(ctx):
    return ctx.chip.idle_pct
