"""The readers PR 36 added, on a hand-built trace and hand-written
program texts: ops joined to the module that encloses them, one
instruction name in two modules kept apart, each reader's value, rows
that sum to ``busy_ns``, the coverage beside one unscoped op, ``None``
where a cell has no such scope or the program no scope tables. CPU; no
backend is touched while this file is imported."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# One chip, times in ns. The grad program runs at 600 (cut off), 1000,
# 2000 and 3000, the apply program after it at 1700 and 2700: the window
# is [1000, 3000], two steps. A step's ops: a matmul fusion under
# hvd.attn.proj (mixed: a norm folded in) 1000-1200; a ``while`` of the
# dispatch 1200-1600 whose body's gather runs 1250-1450 inside it; the
# recomputed ffn 1600-1650; a copy with no metadata 1650-1700; then the
# apply program's OWN %fusion.1, 1700-1900, which is hvd.apply there.
XSPACE = r"""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 10 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 700000 }
    events { metadata_id: 11 offset_ps: 1700000 duration_ps: 200000 }
    events { metadata_id: 10 offset_ps: 2000000 duration_ps: 700000 }
    events { metadata_id: 11 offset_ps: 2700000 duration_ps: 200000 }
    events { metadata_id: 10 offset_ps: 3000000 duration_ps: 700000 }
  }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 1200000 duration_ps: 400000 }
    events { metadata_id: 3 offset_ps: 1250000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 1600000 duration_ps: 50000 }
    events { metadata_id: 5 offset_ps: 1650000 duration_ps: 50000 }
    events { metadata_id: 1 offset_ps: 1700000 duration_ps: 200000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 2200000 duration_ps: 400000 }
    events { metadata_id: 3 offset_ps: 2250000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 2600000 duration_ps: 50000 }
    events { metadata_id: 5 offset_ps: 2650000 duration_ps: 50000 }
    events { metadata_id: 1 offset_ps: 2700000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0:T(8,128)} %a.1), kind=kOutput, calls=%fused_computation.1" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = (s32[], f32[8,8]{1,0:T(8,128)}) while((s32[], f32[8,8]{1,0:T(8,128)}) %copy.1), condition=%cond.1, body=%body.1" } }
  event_metadata { key: 3 value { id: 3 name: "%gather.1 = f32[8,8]{1,0:T(8,128)} gather(f32[8,8]{1,0:T(8,128)} %gte.1)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = (f32[8,8]{1,0:T(8,128)}, f32[8,8]{1,0:T(8,128)}) fusion(f32[8,8]{1,0:T(8,128)} %fusion.1), kind=kLoop, calls=%fused_computation.2" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.1 = f32[8,8]{1,0:T(8,128)} copy(f32[8,8]{1,0:T(8,128)} %fusion.3)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_hvd_grad(1)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_hvd_apply(2)" } }
}
"""

APPLY = r"""HloModule jit_hvd_apply, is_scheduled=true

%fused_computation.1 (p.1: f32[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  ROOT %sub.1 = f32[8,8]{1,0} subtract(%p.1, %p.1), metadata={op_name="jit(hvd_apply)/hvd.apply/sub"}
}

ENTRY %main.2 (a.1: f32[8,8]) -> f32[8,8] {
  %a.1 = f32[8,8]{1,0} parameter(0)
  ROOT %fusion.1 = f32[8,8]{1,0} fusion(%a.1), kind=kLoop, calls=%fused_computation.1
}
"""


def _grad_text():
    sys.path.insert(0, os.path.join(ROOT, "tests", "single"))
    from test_device_scopes import HLO

    return HLO


def _ctx(monkeypatch, texts=None):
    """The hand-built chip; ``texts`` stand in for what the program
    would hand out (``scopes.program_texts``)."""
    from jax.profiler import ProfileData

    from chipbench import scopes, xplane

    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    (chip,) = xplane.chips(profile)
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)
    ctx = types.SimpleNamespace(
        chip=chip, profile=profile, lane=types.SimpleNamespace(rank=0),
        traffic={"lane": "spmd"})
    if texts is not None:
        monkeypatch.setattr(scopes, "program_texts", lambda _ctx: texts)
    return ctx


@pytest.fixture
def ctx(monkeypatch):
    return _ctx(monkeypatch, [("jit_hvd_grad", _grad_text()),
                              ("jit_hvd_apply", APPLY)])


def _read(name, ctx):
    from chipbench import child

    return child.load_reader(name).read(ctx)


def test_rows_sum_to_busy_and_modules_keep_their_names_apart(ctx):
    from chipbench import scopes

    rows, unscoped = scopes.reduction(ctx)
    assert sum(rows.values()) == ctx.chip.busy_ns == 2 * 900
    by = {(r.module, r.scope, r.phase, r.kind, r.mixed): ns
          for r, ns in rows.items()}
    assert by == {
        ("jit_hvd_grad", "hvd.attn.proj", "forward", "fusion:kOutput",
         True): 400,
        # the while's SELF time: its 400 less the body's 200
        ("jit_hvd_grad", "hvd.moe.dispatch", "forward", "while",
         False): 400,
        ("jit_hvd_grad", "hvd.moe.dispatch", "forward", "gather",
         False): 400,
        ("jit_hvd_grad", "hvd.ffn", "recomputed", "fusion:kLoop",
         False): 100,
        ("jit_hvd_grad", None, "forward", "copy", False): 100,
        # the same instruction name, the other program's table
        ("jit_hvd_apply", "hvd.apply", "forward", "fusion:kOutput",
         False): 400}
    assert unscoped == {("jit_hvd_grad", "copy.1"): 100}


@pytest.mark.parametrize("spans_ns,want", [
    # nested: the parent's self time and the child's whole
    ([(0, 10), (2, 4)], [8, 2]),
    # overlapping, neither inside the other (an async copy's ``done``
    # beside the next op): the overlap goes to the later one, ONCE
    ([(0, 10), (5, 15)], [5, 10]),
    # a gap, two that begin together, one inside both
    ([(0, 4), (6, 12), (6, 9), (7, 8)], [4, 3, 2, 1]),
])
def test_every_instant_goes_to_one_op(spans_ns, want):
    from chipbench import scopes, xplane

    events = [xplane.Event(f"%op.{i}", s, e, None)
              for i, (s, e) in enumerate(spans_ns)]
    got = scopes.innermost_ns(events)
    assert [ns for _, ns in got] == want
    assert sum(want) == xplane.total(xplane.union(spans_ns))


@pytest.mark.parametrize("metric,want", [
    ("scope_coverage_pct.lm", 100.0 * 1700 / 1800),
    ("scope_coverage_pct.cnn", 100.0 * 1700 / 1800),
    ("recompute_ms_per_step", 100 / 1e6 / 2),
    ("proj_ms_per_step", 400 / 1e6 / 2),
    ("ffn_ms_per_step", 100 / 1e6 / 2),
    ("moe_move_ms_per_step", 800 / 1e6 / 2),
    # scopes this program has none of: None, not 0
    ("norm_rope_ms_per_step", None),
    ("head_loss_ms_per_step", None),
    ("moe_route_ms_per_step", None),
])
def test_each_reader_on_the_hand_built_trace(ctx, metric, want):
    got = _read(metric, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_run_says_its_table_once(ctx, capsys):
    _read("proj_ms_per_step", ctx)
    _read("ffn_ms_per_step", ctx)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    (line,) = [ln for ln in lines if ln.get("event") == "scopes"]
    assert line["rows_minus_busy_ns"] == 0 and line["steps"] == 2
    assert line["coverage_pct"] == pytest.approx(100.0 * 1700 / 1800)
    assert line["mixed_pct"] == pytest.approx(100.0 * 400 / 1800)
    assert line["scope_x_phase_ms"]["hvd.ffn"] == {
        "recomputed": pytest.approx(100 / 1e6 / 2)}
    assert line["unscoped_ms"] == [["jit_hvd_grad", "copy.1",
                                    pytest.approx(100 / 1e6 / 2)]]


def test_a_program_the_tables_do_not_know_is_unscoped_not_lost(
        monkeypatch):
    from chipbench import scopes

    ctx = _ctx(monkeypatch, [("jit_hvd_grad", _grad_text())])  # no apply
    rows, unscoped = scopes.reduction(ctx)
    assert sum(rows.values()) == ctx.chip.busy_ns
    assert unscoped[("jit_hvd_apply", "fusion.1")] == 400
    assert _read("scope_coverage_pct.lm", ctx) \
        == pytest.approx(100.0 * 1300 / 1800)


STALE = APPLY.replace("ROOT %fusion.1 =", "ROOT %fusion.2 =")


@pytest.mark.parametrize("metric", [
    "scope_coverage_pct.lm", "proj_ms_per_step", "recompute_ms_per_step"])
def test_a_table_of_another_program_is_refused_not_joined(
        monkeypatch, capsys, metric):
    """``jit_hvd_apply`` ran a ``%fusion.1`` that the table under its
    name does not hold (the eager lane changed and ``scopes.py``'s copy
    of its programs did not): no reader gives a number, the line says
    why."""
    ctx = _ctx(monkeypatch, [("jit_hvd_grad", _grad_text()),
                             ("jit_hvd_apply", STALE)])
    assert _read(metric, ctx) is None
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert line["event"] == "scopes" and "fusion.1" in line["refused"]
    assert line["refused"].startswith("StaleTables: jit_hvd_apply: 1 ")
    assert _read(metric, ctx) is None and not capsys.readouterr().out


def test_a_copy_of_the_lane_that_compiles_anew_is_refused(
        monkeypatch, capsys):
    """The eager lane's programs are lowered from a copy of
    ``lanes/hvd.py``'s expressions; a copy the compile cache does not
    know is not what ran."""
    from chipbench import scopes
    from horovod_tpu.utils import spans

    monkeypatch.setattr(spans, "_PROGRAMS", {})   # this process filed none
    ctx = _ctx(monkeypatch)
    ctx.traffic = {"lane": "hvd"}
    compiled = iter([3, 4])
    monkeypatch.setattr(scopes, "_compiled_anew", lambda: next(compiled))
    monkeypatch.setattr(scopes, "_eager_lane_programs", lambda _ctx: [])
    assert _read("scope_coverage_pct.cnn", ctx) is None
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert line["refused"].startswith("NotWhatRan: ")
    # the cache knew both: joined
    ctx = _ctx(monkeypatch)
    ctx.traffic = {"lane": "hvd"}
    monkeypatch.setattr(scopes, "_compiled_anew", lambda: 3)
    assert _read("scope_coverage_pct.cnn", ctx) == 0.0


@pytest.mark.parametrize("metric", [
    "scope_coverage_pct.lm", "recompute_ms_per_step", "proj_ms_per_step",
    "norm_rope_ms_per_step", "ffn_ms_per_step", "head_loss_ms_per_step",
    "moe_route_ms_per_step", "moe_move_ms_per_step"])
def test_a_program_without_scope_tables_reads_none(monkeypatch, metric):
    """The parent commit: ``horovod_tpu.utils.spans`` has no
    ``program_texts``; nothing raises and the line leaves the metric
    out."""
    from horovod_tpu.utils import spans

    monkeypatch.delattr(spans, "program_texts")
    assert _read(metric, _ctx(monkeypatch)) is None


def test_the_report_prints_the_table_of_a_kept_trace(tmp_path, capsys):
    from jax.profiler import ProfileData

    from chipbench import scopes

    trace = tmp_path / "rank0.xplane.pb"
    trace.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    texts = []
    for name, text in (("grad", _grad_text()), ("apply", APPLY)):
        path = tmp_path / f"rank0.{name}.hlo.txt"
        path.write_text(text)
        texts.append(str(path))
    scopes._report(str(trace), texts)
    out = capsys.readouterr().out
    assert "rows - busy = 0 ns" in out and "coverage 94.44%" in out
    assert "jit_hvd_grad  copy.1" in out and "hvd.moe.dispatch" in out


# The cells whose metric set no test pins. ``mistral7b.hvd4.b2s4096``,
# ``trinitymini.spmd.b2s8192`` and ``lfm2moe.spmd.b2s8192`` are pinned as
# PRs 25, 32 and 34 left them (``test_program_spans.py``,
# ``test_trinity_cell.py``, ``test_lfm2_cell.py``): they join in the
# ``benchmark`` PR that moves those pins (ROADMAP S11).
LM = ["mistral7b.spmd.b2s4096", "olmoe1b7b.spmd.b2s4096"]
NEW = {"scope_coverage_pct.lm": LM,
       "scope_coverage_pct.cnn": ["resnet50.hvd1.b256"],
       "recompute_ms_per_step": LM, "proj_ms_per_step": LM,
       # OLMoE has no dense FFN and no shared expert: nothing to read
       "norm_rope_ms_per_step": LM, "ffn_ms_per_step": LM[:1],
       "head_loss_ms_per_step": LM, "moe_route_ms_per_step": LM[1:],
       "moe_move_ms_per_step": LM[1:]}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_entries_are_listed_for_their_cells(metric):
    """What PR 36 appends to ``per_layer``, each a trace-read metric of
    a layer the benchmark has, for the cells above."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == NEW[metric]
    assert entry["source"] == "device_trace"
    assert entry["layer"] == ("device" if "coverage" in metric
                              else "model")
    assert entry["moves"] == ("images_per_s" if metric.endswith(".cnn")
                              else "tokens_per_s")
    # appended: what the benchmark had comes first, in its old order
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(metric) > names.index("short_conv_ms_per_step")


def test_the_readers_name_only_scopes_of_the_table():
    """The readers name a scope without its ``hvd.`` (see
    ``scopes.ms_per_step``); a name outside ``SCOPES`` raises there
    instead of reading None like a layer the cell does not have, and
    every name a reader passes is in the table."""
    import re

    from chipbench import scopes
    from horovod_tpu.utils.spans import PHASES, SCOPES

    named = set()
    folder = os.path.join(ROOT, "chipbench", "layer_metrics")
    for name in os.listdir(folder):
        with open(os.path.join(folder, name)) as f:
            call = re.search(r"scopes\.ms_per_step\(ctx([^)]*)\)", f.read())
        if call:
            named |= {"hvd." + s
                      for s in re.findall(r'"([a-z0-9_.]+)"', call.group(1))
                      if s not in PHASES}
    assert named and named <= SCOPES
    # between them the readers leave out only what no entry asks for
    assert SCOPES - named == {
        "hvd.attn.core", "hvd.conv.chain", "hvd.moe.experts", "hvd.apply",
        "hvd.allreduce", "hvd.cnn.stem", "hvd.cnn.stage1",
        "hvd.cnn.stage2", "hvd.cnn.stage3", "hvd.cnn.stage4",
        "hvd.cnn.head"}
    with pytest.raises(ValueError, match="moe.dispach"):
        scopes.ms_per_step(None, "moe.dispach")
