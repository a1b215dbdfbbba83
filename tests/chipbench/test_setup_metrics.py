"""The six readers PR 52 adds, each on a hand-made compile log, marks
and ``ctx``: what ``setup_s`` is made of by the program's own account
(``horovod_tpu/utils/compile_cache.py:compile_events``,
``horovod_tpu/utils/spans.py:marks``; docs/metrics.md "Set-up: the
compile log and the start-up marks"). CPU; nothing is compiled."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LISTED = ["mistral7b.spmd.b2s4096", "olmoe1b7b.spmd.b2s4096",
          "resnet50.hvd1.b256"]
NEW = {"setup_first_step_s": ("entry points", "s", LISTED),
       "setup_trace_lower_s": ("compile cache", "s", LISTED),
       "setup_cache_read_s": ("compile cache", "s", LISTED),
       "setup_step_compile_s": ("compile cache", "s", LISTED),
       "compiles_in_window": ("compile cache", "count", LISTED),
       "hvd_init_s": ("eager Horovod lane", "s", LISTED[2:])}

# Two warm-up and three calibration steps, then a window of two priming
# steps and eight more: set-up is at_step <= 5, the window's last step
# is the fifteenth.
CTX = types.SimpleNamespace(
    traffic={"warmup_steps": 2, "calibration_steps": 3},
    steps_in_window=10)


def _log():
    from horovod_tpu.utils.compile_cache import CompileEvent as E

    return [
        # before the first step: weights, and the benchmark's lowering
        E("init", "trace", 0.5, None, None, 9.0, 0, False, 0),
        E("init", "lower", 0.25, None, None, 9.5, 0, False, 0),
        E("init", "compile", 2.0, "hit", 1.5, 9.75, 0, False, 0),
        # the first step: the kernels' jits inside the grad program's
        # trace are in its seconds and no records of their own
        E("hvd_grad", "trace", 3.0, None, None, 12.0, 1, True, 7),
        E("hvd_grad", "lower", 1.0, None, None, 15.0, 1, True, 0),
        E("hvd_grad", "compile", 4.0, "miss", 0.0, 16.0, 1, True, 0),
        # between calibration and priming: still set-up
        E("agree", "compile", 0.125, "none", 0.0, 21.0, 5, False, 0),
        # the first priming step, the window's last step, both inside
        E("hvd_apply", "trace", 0.5, None, None, 22.0, 6, True, 2),
        E("hvd_apply", "lower", 0.25, None, None, 22.1, 6, True, 0),
        E("hvd_apply", "compile", 8.0, "miss", 0.0, 22.5, 6, True, 0),
        E("hvd_grad", "lower", 16.0, None, None, 31.0, 15, True, 0),
        # after the last step returned: the checks, and a step of theirs
        E("reference", "trace", 32.0, None, None, 40.0, 15, False, 0),
        E("reference", "compile", 64.0, "hit", 60.0, 72.0, 15, False, 0),
        E("hvd_grad", "compile", 128.0, "miss", 0.0, 140.0, 16, True, 0),
    ]


@pytest.fixture
def program(monkeypatch):
    from horovod_tpu.utils import compile_cache, spans

    monkeypatch.setattr(compile_cache, "compile_events", _log)
    monkeypatch.setattr(spans, "marks", lambda: {
        "hvd.imported": 0.5, "hvd.cache.enabled": 2.5, "hvd.init": 2.75,
        "hvd.init.core": 3.0, "hvd.init.plane": 8.25,
        "hvd.step.first": 12.0, "hvd.step.first_dispatched": 20.5})
    return compile_cache, spans


def _read(name, ctx=CTX):
    from chipbench import child

    return child.load_reader(name).read(ctx)


def test_the_log_splits_into_set_up_window_and_after_by_step(program):
    from chipbench import child

    setup, window, after = child.load_reader(
        "compiles_in_window").split(CTX)
    assert [len(part) for part in (setup, window, after)] == [7, 4, 3]
    assert {e.at_step for e in setup} == {0, 1, 5}
    assert {(e.at_step, e.in_step) for e in window} \
        == {(6, True), (15, True)}
    assert {(e.at_step, e.in_step) for e in after} \
        == {(15, False), (16, True)}


@pytest.mark.parametrize("metric,want", [
    ("setup_first_step_s", 12.0),
    ("setup_trace_lower_s", 0.5 + 0.25 + 3.0 + 1.0),
    ("setup_cache_read_s", 1.5),
    ("setup_step_compile_s", (2.0 - 1.5) + 4.0 + 0.125),
    # hvd_apply's trace and compile; a lowering alone is not counted
    ("compiles_in_window", 2.0),
    ("hvd_init_s", 8.25 - 2.75),
])
def test_each_reader_reads_its_part(program, metric, want):
    got = _read(metric)
    assert isinstance(got, float) and got == want


@pytest.mark.parametrize("metric", [
    "setup_trace_lower_s", "setup_cache_read_s", "setup_step_compile_s",
    "compiles_in_window"])
def test_nothing_to_count_reads_zero_not_none(program, monkeypatch,
                                              metric):
    compile_cache, _ = program
    monkeypatch.setattr(compile_cache, "compile_events", lambda: [])
    got = _read(metric)
    assert got == 0.0 and isinstance(got, float)
    # a set-up with no record in it, beside checks that have some
    monkeypatch.setattr(compile_cache, "compile_events",
                        lambda: _log()[-3:])
    assert _read(metric) == 0.0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_program_without_the_log_or_the_marks_reads_nothing(
        program, monkeypatch, metric):
    """The parent of PR 52 has the modules and not the functions: a
    reader returns ``None`` and does not raise, and the line leaves the
    metric out."""
    compile_cache, spans = program
    monkeypatch.delattr(compile_cache, "compile_events")
    monkeypatch.delattr(spans, "marks")
    assert _read(metric) is None


def test_a_mark_not_reached_reads_nothing(program, monkeypatch):
    _, spans = program
    monkeypatch.setattr(spans, "marks", lambda: {"hvd.imported": 0.5})
    assert _read("setup_first_step_s") is None
    assert _read("hvd_init_s") is None     # the spmd lane has no init


def test_the_six_entries_stand_at_the_end_for_the_cells_named():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    for m in bench["per_layer"][-6:]:
        layer, unit, cells = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "setup_s", "workloads": cells}
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".py"))
    # what the benchmark had comes first, in its old order
    assert bench["per_layer"][-7]["name"] == "mtp_ms_per_step"
