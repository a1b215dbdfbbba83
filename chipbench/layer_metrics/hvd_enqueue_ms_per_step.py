"""The program's ``hvd.enqueue`` span per traced step: the user's thread
flattening the gradient tree, registering every leaf with the device
plane and enqueueing it to the core."""

from chipbench import spans


def read(ctx):
    return spans.ms_per_step(ctx, "hvd.enqueue")
