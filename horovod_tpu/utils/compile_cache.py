"""Where JAX's persistent compilation cache lives for this checkout.

Every program that compiles for the chip (``chip_smoke.py`` children,
``chipbench/run.py``'s cells) calls :func:`enable_compile_cache`
before its first compile, so processes that follow one another — a
smoke's phases, the ranks of a launcher's next run, a second run in the
same checkout — find executables instead of paying the compile again.

The directory is part of the cache key's lookup, so it must not move:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself; no directory is set in code then), otherwise the
fixed ``<checkout>/.jax_cache`` (git-ignored) — never a temp name, a pid
or a timestamp. Tests keep the cache off (the one that checks the ranks'
entries switches it on in child processes, in a temporary directory).

One more thing has to hold still for the cache to hit: a pallas kernel
is serialized into its program together with the MLIR locations of the
trace, by jax's default the Python traceback, so the same train step
gets a different cache key from every call site — and from the same
call site after any edit that moves a line in any frame above it
(measured on the v5e, PR 21: three ~37 s compiles of one grad program in
one smoke run). Locations therefore carry NO frame at all
(``jax_traceback_in_locations_limit=0``): what is left of a location is
the operation's name stack, ``jit(hvd_grad)/transpose(jvp(...))/
checkpoint/rematted_computation/hvd.ffn/dot_general``, which is the
program's own structure and moves with no caller and no line. Until
PR 36 the cut was ``jax_include_full_tracebacks_in_locations=False``
(the op's own file and line stayed, so every checkout and every edit of
a model file had keys of its own). In that mode jax 0.9.0 also writes
the bare primitive as ``op_name`` and drops the name stack of everything
``jax.checkpoint`` re-emits, the recomputed forward and the whole
backward of a layer body, so no device scope
(``utils/spans.py:scope``) reached two thirds of a grad program.

The name stack is what ``spans.scope_table`` reads out of a compiled
program's text, so the text the cache hands back has to be this
program's: jax leaves metadata out of the cache key by default ("
executables loaded from the cache may have stale metadata"), and an
entry written before a scope was added or renamed would answer with the
old names. ``jax_compilation_cache_include_metadata_in_key`` is
therefore on; with no frame in any location the metadata in the key is
the name stacks and nothing else.

Who writes what, where (one directory, every process a writer):

- jax 0.9.0 writes an entry only in the process whose ``jax.distributed``
  process id is 0 (``jax/_src/compiler.py:_cache_write``: "contention
  for writes on some filesystems"); any process reads. The ranks of a
  multi-process job (``horovodrun --tpu-pod``; ``xla_ici.enable()``
  gives rank r process id r) would compile again on every launch, all
  but rank 0. :func:`enable_compile_cache` therefore makes every
  process a writer (:func:`_let_every_rank_write`, which says what it
  leans on). ``jax.distributed`` itself, and its client, which orbax's
  multi-process checkpoints need, stay as jax has them.
- A program that runs on one chip holds that chip in its key (jax strips
  the device assignment from the key on ``gpu`` alone), so four ranks
  keep four copies of the grad and apply programs, each found again only
  by the rank on the same chip. One copy for all would need an
  executable that is not bound to a device. The multi-process program
  (``jit_hvd_allreduce``) has one key on all ranks; all four write it.
- Two writers of one key: with a maximum size set
  (``JAX_COMPILATION_CACHE_MAX_SIZE``, as the chip machine does) jax's
  ``LRUCache`` takes a file lock around every read and write, and
  ``put`` skips a key that exists: the first writer wins and nobody
  reads a half-written file. With no maximum there is no lock, and a
  reader can meet a torn entry; jax then warns and compiles
  (``jax_raise_persistent_cache_errors`` is off by default and nothing
  here turns it on): slower once, never wrong.
- Past the maximum size jax evicts the entries read longest ago, and
  their ranks' next launch compiles again. Measured sizes: PERF.md.

What this process traced, lowered and compiled is logged here too
(:func:`compile_events`, :func:`compile_stats`; docs/metrics.md "Set-up:
the compile log and the start-up marks"): jax reports every trip through
its trace, lowering and compile paths and every cache hit and miss to
listeners, and :func:`enable_compile_cache` registers this module's, so
every entry point that switches the cache on has the log.
"""

import collections
import inspect
import logging
import os
import threading
import warnings

from horovod_tpu.utils import spans

logger = logging.getLogger(__name__)

_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

CompileEvent = collections.namedtuple(
    "CompileEvent", "program phase seconds cache cache_read_s t at_step "
                    "in_step inner")
CompileEvent.__doc__ = """One trip of one program through one phase of
jax's compile path: ``program`` is the jitted function's bare name
(``hvd_grad``; jax says ``jit(hvd_grad)`` when it lowers and compiles);
``phase`` is ``trace`` (Python to jaxpr), ``lower`` (jaxpr to MLIR: the
Mosaic lowerings of the kernels happen here) or ``compile`` (XLA, or the
read of the persistent cache in its place); ``seconds`` is what jax
timed. A ``compile`` record says what the persistent ``cache`` did
(``hit``, ``miss`` = compiled and written, ``none`` = not cacheable,
too quick to be kept, or the cache is off) and the ``cache_read_s`` of
its seconds that went into reading and deserialising the entry (0.0
unless a hit); both are ``None`` in the other phases. ``t`` is when the
trip began, in seconds since the process began (``spans.since_start``);
``at_step`` the steps begun by then (``spans.steps_begun()`` when the
record is written) and ``in_step`` whether the last of them had not
returned yet. jax also times every ``jit`` traced INSIDE another (each
kernel's wrapper, each ``jax.numpy`` function: thousands of trips of a
tenth of a millisecond in one grad program); such a trip is no record,
its seconds are in the outermost one's already, and ``inner`` counts
the trips that began and ended inside this one."""

# A process of this repo compiles tens of programs of its own and a
# hundred eager one-op programs (``convert_element_type``,
# ``broadcast_in_dim``), which take the same three trips: 125 records in
# a run of the Mistral cell (PR 52). The records stop here, the totals
# go on.
LOG_CAP = 4096

_lock = threading.Lock()   # programs compile on the core's thread too
_listening = False
_thread = threading.local()   # trips under way (depth) and ended inside
#                               the outermost (inner); the cache's word on
#                               the compile under way (no name on that event)
_log = []       # the first LOG_CAP CompileEvents
_dropped = 0    # records past the cap: in the totals, not in the log
_totals = {}    # program -> what its records add up to, never capped
_TOTAL_KEYS = ("trace_s", "lower_s", "compile_s", "cache_read_s",
               "backend_compiles", "cache_hits", "cache_misses")


def _on_start(name, _value, **_):
    if name in _PHASES:   # jax's timer is entered: record_scalar
        _thread.depth = getattr(_thread, "depth", 0) + 1


def _on_event(name, **_):
    word = _CACHE_EVENTS.get(name)
    if word:
        _thread.cache = word


def _on_duration(name, seconds, fun_name="", **_):
    global _dropped
    if name == _RETRIEVAL_EVENT:
        _thread.cache_read_s = seconds
        return
    phase = _PHASES.get(name)
    if phase is None:
        return
    depth = _thread.depth = max(getattr(_thread, "depth", 1) - 1, 0)
    if depth:   # inside another trip: its seconds are in that one's
        _thread.inner = getattr(_thread, "inner", 0) + 1
        if phase != "compile":
            return
        inner = 0
    else:
        inner, _thread.inner = getattr(_thread, "inner", 0), 0
    cache = cache_read_s = None
    if phase == "compile":
        cache = getattr(_thread, "cache", "none")
        cache_read_s = getattr(_thread, "cache_read_s", 0.0)
        _thread.cache, _thread.cache_read_s = "none", 0.0
    program = fun_name
    if program.startswith("jit(") and program.endswith(")"):
        program = program[4:-1]
    now, begun = spans.since_start(), spans.steps_begun()
    event = CompileEvent(
        program, phase, seconds, cache, cache_read_s,
        None if now is None else now - seconds, begun,
        begun > spans.steps_returned(), inner)
    with _lock:
        if len(_log) < LOG_CAP:
            _log.append(event)
        else:
            _dropped += 1
        total = _totals.get(program)
        if total is None:
            total = _totals[program] = dict.fromkeys(_TOTAL_KEYS, 0)
        if phase != "compile":
            total[phase + "_s"] += seconds
            return
        total["compile_s"] += seconds - cache_read_s
        total["cache_read_s"] += cache_read_s
        total["backend_compiles"] += cache != "hit"
        total["cache_hits"] += cache == "hit"
        total["cache_misses"] += cache == "miss"


def compile_events():
    """The compile log: a :class:`CompileEvent` for each outermost trip
    of a program through a phase of jax's compile path since
    :func:`enable_compile_cache`, oldest first, the first
    :data:`LOG_CAP` of them (``compile_stats()["events_dropped"]`` counts
    the rest). "Which step recompiled?" is ``[e for e in compile_events()
    if e.at_step > 0 and e.phase == "compile"]``."""
    with _lock:
        return list(_log)


def compile_stats():
    """What this process compiled since :func:`enable_compile_cache`, as
    the compile log adds up, the records past its cap included:
    ``cache_hits`` and ``cache_misses`` of the persistent cache (a miss
    is counted when the entry is written, so a program too small or too
    quick to be cached is neither), ``backend_compiles`` (programs that
    went through jax's compile path and were not fetched from the
    cache) and ``compile_s``, the seconds that took (time in the
    compile path less time spent reading the cache:
    ``cache_retrieval_s``); ``trace_s`` and ``lower_s``, the seconds in
    jax's trace and lowering paths (a trip inside another is in the
    outer one's seconds, and counted there); ``by_program``, the same
    sums for each program (``cache_read_s`` there); ``events_dropped``.
    A compile that an eager operation starts while an outer function is
    being traced is in ``compile_s`` and in that ``trace_s``. All zero
    before the cache is switched on."""
    with _lock:
        by_program = {p: dict(total) for p, total in _totals.items()}
        dropped = _dropped
    stats = {key: sum(total[key] for total in by_program.values())
             for key in _TOTAL_KEYS}
    stats["cache_retrieval_s"] = stats.pop("cache_read_s")
    return dict(stats, by_program=by_program, events_dropped=dropped)


def _let_every_rank_write():
    """Make this process a writer of the persistent cache whatever its
    ``jax.distributed`` process id, without touching that id.

    Leans on two private functions of jax 0.9.0, looked up by name and
    arguments: ``compiler._cache_write``, which the compile path calls
    through its module after every miss and which returns at once where
    the process id is not 0, and ``compilation_cache
    .put_executable_and_time``, which does the writing. Process 0, and
    whatever jax would not write anywhere (host callbacks, a compile
    under ``jax_persistent_cache_min_compile_time_secs``), stay with
    jax's function. Where either looks different, jax is left as it is
    and the rank's log says so.
    """
    import jax
    from jax._src import compilation_cache, compiler, distributed

    jax_write = getattr(compiler, "_cache_write", None)
    put = getattr(compilation_cache, "put_executable_and_time", None)
    try:
        seam = [list(inspect.signature(jax_write).parameters),
                list(inspect.signature(put).parameters)]
    except (TypeError, ValueError):   # gone, or not a function
        seam = None
    if seam != [["cache_key", "compile_time_secs", "module_name", "backend",
                 "executable", "host_callbacks"],
                ["cache_key", "module_name", "executable", "backend",
                 "compile_time"]]:
        logger.warning(
            "jax %s: the persistent cache's writer is not the one this "
            "module knows (jax 0.9.0); ranks other than 0 will not write "
            "compile-cache entries and compile again on every launch",
            jax.__version__)
        return

    def write_on_every_rank(cache_key, compile_time_secs, module_name,
                            backend, executable, host_callbacks):
        if (distributed.global_state.process_id == 0 or host_callbacks
                or compile_time_secs
                < jax.config.jax_persistent_cache_min_compile_time_secs):
            return jax_write(cache_key, compile_time_secs, module_name,
                             backend, executable, host_callbacks)
        try:
            put(cache_key, module_name, executable, backend,
                int(compile_time_secs))
        except Exception as ex:  # noqa: BLE001 — as jax's writer does
            if jax.config.jax_raise_persistent_cache_errors:
                raise
            warnings.warn(
                "Error writing persistent compilation cache entry for "
                f"'{module_name}': {type(ex).__name__}: {ex}")

    compiler._cache_write = write_on_every_rank


# <checkout>/.jax_cache: this file is <checkout>/horovod_tpu/utils/.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on, and the compile log
    (:func:`compile_events`, :func:`compile_stats`) with it; returns the
    cache's directory.

    Must run before the process's first compile (the cache is
    initialized once, at first use).
    """
    global _listening
    import jax

    with _lock:
        listen, _listening = not _listening, True
    if listen:
        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _let_every_rank_write()
    # Name stacks whole, no frame behind them, and in the key: see the
    # module's docstring.
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    spans.mark("hvd.cache.enabled")
    return placed or CHECKOUT_CACHE_DIR
