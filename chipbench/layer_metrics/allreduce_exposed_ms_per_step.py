"""Time inside all-reduce ops during which no other op runs on this
rank's chip, per traced step."""

from chipbench import xplane


def read(ctx):
    chip = ctx.chip
    if not chip.steps or ctx.lane.size < 2:
        return None
    return chip.exposed_ns(xplane.is_all_reduce) / 1e6 / chip.steps
