"""Time of the program's ``hvd.spmd.step`` span per traced step: the
host dispatching the grad and the apply program of
``make_split_train_step``. Against the step time it is the host's
headroom."""

from chipbench import spans


def read(ctx):
    return spans.ms_per_step(ctx, "hvd.spmd.step")
