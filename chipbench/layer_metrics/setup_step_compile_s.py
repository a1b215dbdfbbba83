"""Seconds of set-up the program spent compiling, by its own compile
log: ``seconds`` less ``cache_read_s`` of the ``compile`` records of
``horovod_tpu.utils.compile_cache.compile_events()`` up to the last
calibration step. What ``setup_compile_s`` would read without the
programs of the output checks, which compile after the window; near 0
on a warm cache (programs too quick to be kept are compiled in every
run). 0.0 where set-up compiled nothing, ``None`` for a program without
the log."""

from chipbench.layer_metrics import compiles_in_window


def read(ctx):
    return compiles_in_window.setup_seconds(
        ctx, lambda e: e.seconds - e.cache_read_s
        if e.phase == "compile" else 0.0)
