"""The compile log (``horovod_tpu/utils/compile_cache.py``;
docs/metrics.md "Set-up: the compile log and the start-up marks"): one
record a program, phase and outermost trip, told apart by step, and
``compile_stats()`` as its sum. Toy programs in two worker processes that
share a temporary cache directory; no model is built."""

import pytest

from tests.single.test_compile_cache_ranks import _cache_every_program
from tests.utils_mp import run_ranks


def _worker_logs(rank, size):
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import make_split_train_step, train_step
    from horovod_tpu.utils import compile_cache, spans

    _cache_every_program()
    x = jnp.arange(6.0)
    x.block_until_ready()                  # the eager one-op programs
    out = {"marks": spans.marks()}

    def events():
        return [tuple(e) for e in compile_cache.compile_events()]

    @jax.jit
    def toy_inner(v):
        return jnp.tanh(v) * 2

    def toy(v):
        return toy_inner(v).sum() + 1

    jitted = jax.jit(toy)
    before = len(events())
    jitted(x).block_until_ready()
    out["first_call"] = events()[before:]
    before = len(events())
    jitted(x).block_until_ready()
    out["second_call"] = events()[before:]

    # Two steps of a split step; the second compiles nothing. Then a
    # program inside a third step's dispatch, and one after it returned.
    ts = make_split_train_step(lambda p, d: jnp.sum((p * d) ** 2),
                               optax.sgd(0.1))
    carry = ts.init(jnp.ones(6))
    before = len(events())
    for _ in range(2):
        _, carry = ts.step(carry, x)
    out["steps"] = events()[before:]
    before = len(events())
    train_step._spanned(
        lambda carry, batch: jax.jit(lambda v: v * 5 - 2)(batch))(None, x)
    out["inside_the_third"] = events()[before:]
    before = len(events())
    jax.jit(lambda v: v * 7 - 3)(x).block_until_ready()
    out["after_the_third"] = events()[before:]
    out["all"] = events()
    out["stats"] = compile_cache.compile_stats()
    out["fields"] = compile_cache.CompileEvent._fields
    out["since_start"] = spans.since_start()
    return out


@pytest.fixture(scope="module")
def logs(tmp_path_factory):
    """(a cold process, a second one on the same cache directory)"""
    env = {"JAX_COMPILATION_CACHE_DIR":
           str(tmp_path_factory.mktemp("compile-log"))}
    return [run_ranks(_worker_logs, 1, timeout=120, env=env)[0]
            for _ in range(2)]


def _records(logs, which, part):
    from horovod_tpu.utils.compile_cache import CompileEvent

    return [CompileEvent(*e) for e in logs[which][part]]


def test_a_record_has_the_fields_the_docs_name(logs):
    assert logs[0]["fields"] == (
        "program", "phase", "seconds", "cache", "cache_read_s", "t",
        "at_step", "in_step", "inner")


def test_a_toy_jit_gives_one_record_a_phase_under_its_bare_name(logs):
    outer = [e for e in _records(logs, 0, "first_call")
             if e.program == "toy"]
    assert [e.phase for e in outer] == ["trace", "lower", "compile"]
    assert all(e.seconds > 0 for e in outer)
    # began in this order, after the cache was switched on, before now
    stamps = [e.t for e in outer]
    assert stamps == sorted(stamps)
    assert logs[0]["marks"]["hvd.cache.enabled"] < stamps[0] \
        < logs[0]["since_start"]


def test_a_second_call_leaves_no_record(logs):
    assert logs[0]["second_call"] == []


def test_a_jit_traced_inside_another_is_no_record_and_counted_once(logs):
    """jax times the inner trace too; it lies inside the outer one,
    whose record counts it (and the two ``jax.numpy`` functions traced
    inside the inner one, and ``sum`` and ``add`` beside it)."""
    first = _records(logs, 0, "first_call")
    assert not [e for e in first if e.program == "toy_inner"]
    (outer,) = [e for e in first if (e.program, e.phase)
                == ("toy", "trace")]
    assert outer.inner >= 3
    assert [e.inner for e in first if e.phase != "trace"] == [0, 0]
    by_program = logs[0]["stats"]["by_program"]
    assert "toy_inner" not in by_program
    assert by_program["toy"]["trace_s"] == outer.seconds


@pytest.mark.parametrize("which,cache", [(0, "miss"), (1, "hit")])
def test_a_compile_record_says_what_the_cache_did(logs, which, cache):
    (toy,) = [e for e in _records(logs, which, "first_call")
              if (e.program, e.phase) == ("toy", "compile")]
    assert toy.cache == cache
    if cache == "hit":
        assert 0 < toy.cache_read_s <= toy.seconds
    else:
        assert toy.cache_read_s == 0.0
    others = [e for e in _records(logs, which, "first_call")
              if e.phase != "compile"]
    assert others and all(e.cache is None and e.cache_read_s is None
                          for e in others)


def test_records_follow_the_two_step_counts(logs):
    """Before any step ``at_step`` is 0; the first step's programs are
    compiled inside it; a compile inside the third step's dispatch is
    told from one after that step returned."""
    assert {(e.at_step, e.in_step)
            for e in _records(logs, 0, "first_call")} == {(0, False)}
    steps = _records(logs, 0, "steps")
    assert {e.program for e in steps} == {"hvd_grad", "hvd_apply"}
    assert {(e.at_step, e.in_step) for e in steps} == {(1, True)}
    inside = _records(logs, 0, "inside_the_third")
    after = _records(logs, 0, "after_the_third")
    assert {e.phase for e in inside} == {e.phase for e in after} \
        == {"trace", "lower", "compile"}
    assert {(e.at_step, e.in_step) for e in inside} == {(3, True)}
    assert {(e.at_step, e.in_step) for e in after} == {(3, False)}


@pytest.mark.parametrize("which", [0, 1])
def test_compile_stats_is_the_sum_of_the_log(logs, which):
    """The keys ``compile_stats()`` had before PR 52, with the meaning
    they had, and the new ones: all of them sums over the records."""
    records = _records(logs, which, "all")
    stats = logs[which]["stats"]
    compiles = [e for e in records if e.phase == "compile"]
    assert stats["events_dropped"] == 0
    assert stats["cache_hits"] == sum(e.cache == "hit" for e in compiles)
    assert stats["cache_misses"] == sum(e.cache == "miss"
                                        for e in compiles)
    assert stats["backend_compiles"] == len(compiles) - stats["cache_hits"]
    assert stats["cache_retrieval_s"] == pytest.approx(
        sum(e.cache_read_s for e in compiles))
    assert stats["compile_s"] == pytest.approx(
        sum(e.seconds for e in compiles) - stats["cache_retrieval_s"])
    for phase in ("trace", "lower"):
        assert stats[phase + "_s"] == pytest.approx(sum(
            e.seconds for e in records if e.phase == phase))
    assert stats["compile_s"] == pytest.approx(sum(
        p["compile_s"] for p in stats["by_program"].values()))
    if which:   # everything the first process wrote is found again
        assert stats["cache_misses"] == stats["backend_compiles"] == 0
        assert stats["cache_hits"] == len(compiles) > 0


def test_the_cap_drops_records_and_counts_them_and_the_sums_go_on(
        monkeypatch):
    from horovod_tpu.utils import compile_cache as cc

    monkeypatch.setattr(cc, "LOG_CAP", 4)
    monkeypatch.setattr(cc, "_log", [])
    monkeypatch.setattr(cc, "_totals", {})
    monkeypatch.setattr(cc, "_dropped", 0)
    monkeypatch.setattr(cc, "_thread", cc.threading.local())
    for n in range(3):
        for event in cc._PHASES:
            cc._on_start(event, 0.0, fun_name=f"jit(op{n})")
            cc._on_duration(event, 0.25, fun_name=f"jit(op{n})")
    kept = cc.compile_events()
    assert [(e.program, e.phase) for e in kept] == [
        ("op0", "trace"), ("op0", "lower"), ("op0", "compile"),
        ("op1", "trace")]
    stats = cc.compile_stats()
    assert stats["events_dropped"] == 5
    assert (stats["trace_s"], stats["lower_s"], stats["compile_s"],
            stats["backend_compiles"]) == (0.75, 0.75, 0.75, 3)
    assert sorted(stats["by_program"]) == ["op0", "op1", "op2"]


def test_threads_file_records_without_losing_one(monkeypatch):
    """Programs compile on the core's thread too: the records and the
    totals are written under one lock, and the depth and the cache's
    word are each thread's own."""
    import sys
    import threading

    from horovod_tpu.utils import compile_cache as cc

    monkeypatch.setattr(cc, "_log", [])
    monkeypatch.setattr(cc, "_totals", {})
    monkeypatch.setattr(cc, "_dropped", 0)
    monkeypatch.setattr(cc, "_thread", threading.local())
    trace, _, compile_ = cc._PHASES
    hit = "/jax/compilation_cache/cache_hits"
    workers, trips = 16, 50

    def work(n):
        for _ in range(trips):
            cc._on_start(trace, 0.0, fun_name="outer")
            cc._on_start(trace, 0.0, fun_name="inner")
            cc._on_duration(trace, 1.0, fun_name="inner")
            cc._on_duration(trace, 2.0, fun_name="outer")
            cc._on_start(compile_, 0.0, fun_name="jit(outer)")
            if n % 2:
                cc._on_event(hit)
                cc._on_duration(cc._RETRIEVAL_EVENT, 0.5)
            cc._on_duration(compile_, 4.0, fun_name="jit(outer)")

    threads = [threading.Thread(target=work, args=(n,))
               for n in range(workers)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    stats = cc.compile_stats()
    n = workers * trips
    assert len(cc.compile_events()) + stats["events_dropped"] == 2 * n
    assert {(e.program, e.phase, e.inner) for e in cc.compile_events()} \
        == {("outer", "trace", 1), ("outer", "compile", 0)}
    assert stats["by_program"] == {"outer": {
        "trace_s": 2.0 * n, "lower_s": 0, "compile_s": 4.0 * n - 0.25 * n,
        "cache_read_s": 0.25 * n, "backend_compiles": n // 2,
        "cache_hits": n // 2, "cache_misses": 0}}
