"""Time of the program's ``hvd.wait`` span per traced step: the user's
thread asleep in the core until the last result of the step's all-reduce
is stored (negotiation and ``hvd.device_exec`` fall into it, on the
core's thread)."""

from chipbench import spans


def read(ctx):
    return spans.ms_per_step(ctx, "hvd.wait")
