"""How far the core's background loop overran its cycle budget
(``hvd.metrics()["cycle"]["overrun_us"]``) per step, over the whole
window: the core's thread held up. A late step caused there shows
here."""

from chipbench import spans


def read(ctx):
    return spans.counter_per_step(ctx, "cycle", "overrun_us", scale=1e-3)
