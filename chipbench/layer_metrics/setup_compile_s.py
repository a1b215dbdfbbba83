"""Seconds this process spent compiling, by the program's own count
(``horovod_tpu.utils.compile_cache.compile_stats()``): time in jax's
compile path less time reading the persistent cache. Nothing compiles
inside the window (``correct`` checks that), so this is set-up's
compilation plus that of the output checks after the window; a warm
cache leaves only programs too small to be cached. ``None`` for a
program without the counter."""


def read(ctx):
    try:
        from horovod_tpu.utils.compile_cache import compile_stats
    except ImportError:
        return None
    return compile_stats()["compile_s"]
