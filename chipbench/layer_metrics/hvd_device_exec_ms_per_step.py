"""Time of the program's ``hvd.device_exec`` spans per traced step: the
core's thread, called back into Python, taking the inputs, looking the
fused program up, launching it and storing the outputs. Host time; the
program's device time is on the device plane under ``jit_hvd_*``."""

from chipbench import spans


def read(ctx):
    return spans.ms_per_step(ctx, "hvd.device_exec")
