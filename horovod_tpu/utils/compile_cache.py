"""Where JAX's persistent compilation cache lives for this checkout.

Every program that compiles for the chip (``chip_smoke.py`` children,
``bench.py``, ``benchmarks/*.py``) calls :func:`enable_compile_cache`
before its first compile, so processes that follow one another — a
smoke's phases, a launcher's ranks, a second run in the same checkout —
find each other's executables instead of paying the compile again.

The directory is part of the cache key's lookup, so it must not move:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself; no directory is set in code then), otherwise the
fixed ``<checkout>/.jax_cache`` (git-ignored) — never a temp name, a pid
or a timestamp. Tests never call this: they keep the cache off.

One more thing has to hold still for the cache to hit: a pallas kernel
is serialized into its program together with the FULL Python traceback
of the trace (jax's default for MLIR locations), so the same train step
gets a different cache key from every call site — and from the same
call site after any edit that moves a line in any frame above it
(measured on the v5e, PR 21: three ~37 s compiles of one grad program in
one smoke run). Locations are therefore cut to the frame of the op.

What the cache did for this process is counted here too
(:func:`compile_stats`): jax reports every trip through its compile path
and every cache hit and miss to listeners, and
:func:`enable_compile_cache` registers this module's, so every entry
point that switches the cache on has the counter.
"""

import os
import threading

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNTED = {"/jax/compilation_cache/cache_hits": "cache_hits",
            "/jax/compilation_cache/cache_misses": "cache_misses"}

_lock = threading.Lock()   # programs compile on the core's thread too
_listening = False
_stats = {"cache_hits": 0, "cache_misses": 0, "programs": 0,
          "compile_path_s": 0.0, "cache_retrieval_s": 0.0}


def _on_event(name, **_):
    key = _COUNTED.get(name)
    if key:
        with _lock:
            _stats[key] += 1


def _on_duration(name, seconds, **_):
    if name == _COMPILE_EVENT:
        with _lock:
            _stats["programs"] += 1
            _stats["compile_path_s"] += seconds
    elif name == _RETRIEVAL_EVENT:
        with _lock:
            _stats["cache_retrieval_s"] += seconds


def compile_stats():
    """What this process compiled since :func:`enable_compile_cache`:
    ``cache_hits`` and ``cache_misses`` of the persistent cache (a miss
    is counted when the entry is written, so a program too small or too
    quick to be cached is neither), ``backend_compiles`` (programs that
    went through jax's compile path and were not fetched from the
    cache) and ``compile_s``, the seconds that took (time in the
    compile path less time spent reading the cache:
    ``cache_retrieval_s``). All zero before the cache is switched on."""
    with _lock:
        s = dict(_stats)
    return {"cache_hits": s["cache_hits"],
            "cache_misses": s["cache_misses"],
            "backend_compiles": s["programs"] - s["cache_hits"],
            "compile_s": s["compile_path_s"] - s["cache_retrieval_s"],
            "cache_retrieval_s": s["cache_retrieval_s"]}

# <checkout>/.jax_cache: this file is <checkout>/horovod_tpu/utils/.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on, and the counters of
    :func:`compile_stats` with it; returns the cache's directory.

    Must run before the process's first compile (the cache is
    initialized once, at first use).
    """
    global _listening
    import jax

    with _lock:
        listen, _listening = not _listening, True
    if listen:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
