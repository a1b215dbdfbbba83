"""Seconds of set-up the program spent reading executables out of the
persistent compile cache and deserialising them, by its own compile log:
``cache_read_s`` of the ``compile`` records of
``horovod_tpu.utils.compile_cache.compile_events()`` up to the last
calibration step. What a warm run pays in the place of the compile. 0.0
where nothing was read (a cold cache), ``None`` for a program without
the log."""

from chipbench.layer_metrics import compiles_in_window


def read(ctx):
    return compiles_in_window.setup_seconds(
        ctx, lambda e: e.cache_read_s if e.phase == "compile" else 0.0)
