"""The core's negotiation rounds (``hvd.metrics()["negotiation_us"]``,
summed) per step, over the whole window: the gather and broadcast of
requests and responses between ranks, on the core's thread."""

from chipbench import spans


def read(ctx):
    return spans.counter_per_step(ctx, "negotiation_us", "sum_us",
                                  scale=1e-3)
