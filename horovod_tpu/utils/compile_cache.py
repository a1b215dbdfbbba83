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
"""

import os

# <checkout>/.jax_cache: this file is <checkout>/horovod_tpu/utils/.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on; returns its directory.

    Must run before the process's first compile (the cache is
    initialized once, at first use).
    """
    import jax

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
