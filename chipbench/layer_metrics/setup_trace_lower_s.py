"""Seconds of set-up the program spent tracing its functions in Python
and lowering them to MLIR (the Mosaic lowerings of the kernels happen
there), by its own compile log: ``trace`` and ``lower`` records of
``horovod_tpu.utils.compile_cache.compile_events()`` up to the last
calibration step (a ``jit`` traced inside another is in the outer
record's seconds and no record of its own). A warm compile cache saves
none of it. 0.0 where set-up traced nothing, ``None`` for a program
without the log."""

from chipbench.layer_metrics import compiles_in_window


def read(ctx):
    return compiles_in_window.setup_seconds(
        ctx, lambda e: e.seconds if e.phase in ("trace", "lower") else 0.0)
