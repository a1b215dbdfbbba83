"""Fleet observatory (docs/fleet.md): rank-seconds ledgers that
reconcile to the microsecond on hand-built and simworld-synthesized
dumps, the SLO grammar/drift/recording contract, breach folding, the
256-rank aggregation latency bar, the live observatory's endpoint
derivation and sick-rank tolerance, and the report.py --fleet CLI. No
core, no processes: everything here is pure interval math plus the
simworld dump synthesizer (r16 gotcha 1)."""

import json
import os
import time

import pytest

from horovod_tpu.simworld import harness
from horovod_tpu.telemetry import (
    critpath,
    fleet,
    perfwatch,
    postmortem,
    report,
    slo,
)

pytestmark = pytest.mark.quick

_UNIX0 = 1_700_000_000_000_000


def _write_dump(path, rank, events, steady0=0, unix0=_UNIX0, size=2):
    header = {"kind": "blackbox_header", "rank": rank, "size": size,
              "epoch": 0, "unix_us": unix0, "steady_us": steady0,
              "fault": {}}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(json.dumps(header) + "\n")
        for seq, ev in enumerate(events):
            f.write(json.dumps({"seq": seq, **ev}) + "\n")
    return path


def _at(wall, steady0=0, unix0=_UNIX0):
    return wall - unix0 + steady0


def _known_events():
    """One rank, two steps, every evidence class at KNOWN offsets:
    step 1 wall 0..100k carries a request (queued 10k..20k, prefill
    20k..30k), a wire span 40k..60k whose wait block covers only
    50k..60k (exposed = 10k), and a 20k retry window 70k..90k; steps
    are separated by a 10k idle gap, step 2 wall 110k..150k is pure
    compute."""
    return [
        {"ts_us": _at(0), "type": "step_begin", "step": 1},
        {"ts_us": _at(10_000), "type": "request", "phase": 0, "rid": 7,
         "aux": 0, "phase_name": "queued"},
        {"ts_us": _at(20_000), "type": "request", "phase": 1, "rid": 7,
         "aux": 0, "phase_name": "prefill"},
        {"ts_us": _at(30_000), "type": "request", "phase": 7, "rid": 7,
         "aux": 0, "phase_name": "done"},
        {"ts_us": _at(60_000), "type": "wire_span", "plane": 0,
         "dur_us": 20_000, "tx_bytes": 1, "rx_bytes": 1},
        {"ts_us": _at(60_000), "type": "wait", "dur_us": 10_000},
        {"ts_us": _at(90_000), "type": "retry_window", "attempt": 1,
         "window_ms": 20},
        {"ts_us": _at(100_000), "type": "step_end", "step": 1,
         "dur_us": 100_000},
        {"ts_us": _at(110_000), "type": "step_begin", "step": 2},
        {"ts_us": _at(150_000), "type": "step_end", "step": 2,
         "dur_us": 40_000},
    ]


# ---- ledger reconciliation --------------------------------------------


def test_ledger_reconciles_known_dump_to_the_microsecond(tmp_path):
    path = _write_dump(str(tmp_path / "blackbox-rank0.jsonl"), 0,
                       _known_events())
    dump = postmortem.load_blackbox(path)[-1]
    l = fleet.ledger_from_dump(dump)
    b = l["buckets"]
    assert l["window_us"] == 150_000
    # The r17 standard: exact integer reconciliation, zero remainder.
    assert sum(b.values()) == l["window_us"]
    assert b == {
        "compute": 90_000,        # step windows minus claimed evidence
        "exposed_wire": 10_000,   # span ∩ wait, NOT the 20k raw span
        "negotiation": 0,
        "serving_prefill": 10_000,
        "serving_decode": 0,
        "serving_queued": 10_000,
        "stall": 20_000,
        "idle": 10_000,           # the inter-step gap 100k..110k
        "unattributed": 0,
    }, b
    # useful = compute + exposed + prefill = 110k of 150k.
    assert l["utilization"] == round(110_000 / 150_000, 6)


def test_explicit_window_books_unseen_time_as_unattributed(tmp_path):
    path = _write_dump(str(tmp_path / "blackbox-rank0.jsonl"), 0,
                       _known_events())
    dump = postmortem.load_blackbox(path)[-1]
    l = fleet.ledger_from_dump(dump, window=(_at(0) + _UNIX0,
                                             _at(200_000) + _UNIX0))
    assert l["window_us"] == 200_000
    assert sum(l["buckets"].values()) == 200_000
    # The 50k past the last event carries no evidence: it must stay
    # visible as a remainder, never be absorbed into compute/idle.
    assert l["buckets"]["unattributed"] == 50_000


def test_default_window_opens_at_first_step_mark(tmp_path):
    """Startup before the first marked step (imports, rendezvous,
    debug-server binds) is not schedulable rank-time: a step-marked
    rank's default window must open at the first step mark, not at the
    earliest recorded event — else every ledger starts with a bogus
    unattributed lead-in."""
    events = [{"ts_us": _at(-30_000), "type": "epoch", "epoch": 1},
              *_known_events()]
    path = _write_dump(str(tmp_path / "blackbox-rank0.jsonl"), 0, events)
    dump = postmortem.load_blackbox(path)[-1]
    l = fleet.ledger_from_dump(dump)
    assert l["window_us"] == 150_000, l
    assert l["buckets"]["unattributed"] == 0
    # An UNMARKED rank (pure serving lane) keeps the first-event open.
    bare = [{"ts_us": _at(5_000), "type": "request", "phase": 0,
             "rid": 1, "aux": 0, "phase_name": "queued"},
            {"ts_us": _at(25_000), "type": "request", "phase": 7,
             "rid": 1, "aux": 0, "phase_name": "done"}]
    path2 = _write_dump(str(tmp_path / "b" / "blackbox-rank0.jsonl"),
                        0, bare)
    l2 = fleet.ledger_from_dump(postmortem.load_blackbox(path2)[-1])
    assert l2["window_us"] == 20_000
    assert l2["buckets"]["serving_queued"] == 20_000


def test_overlapping_evidence_claims_by_priority_without_double_count(
        tmp_path):
    """A retry window overlapping a wire span: stall claims first,
    exposed wire gets only the uncovered remainder — the union claim
    keeps the sum exact no matter how evidence overlaps."""
    path = _write_dump(str(tmp_path / "blackbox-rank0.jsonl"), 0, [
        {"ts_us": _at(0), "type": "step_begin", "step": 1},
        # stall 40k..80k, raw span 50k..90k -> exposed only 80k..90k
        {"ts_us": _at(80_000), "type": "retry_window", "attempt": 1,
         "window_ms": 40},
        {"ts_us": _at(90_000), "type": "wire_span", "plane": 0,
         "dur_us": 40_000, "tx_bytes": 1, "rx_bytes": 1},
        {"ts_us": _at(100_000), "type": "step_end", "step": 1,
         "dur_us": 100_000},
    ])
    l = fleet.ledger_from_dump(postmortem.load_blackbox(path)[-1])
    b = l["buckets"]
    assert sum(b.values()) == l["window_us"] == 100_000
    assert b["stall"] == 40_000
    assert b["exposed_wire"] == 10_000, b
    assert b["compute"] == 50_000


def test_ledger_from_events_is_the_live_twin():
    """Ring-event dicts straight from hvd.events(): ts_us IS the axis
    (zero clock anchors), same reconciliation contract."""
    events = [
        {"seq": 0, "ts_us": 1_000, "type": "step_begin", "step": 1},
        {"seq": 1, "ts_us": 5_000, "type": "wire_span", "plane": 0,
         "dur_us": 2_000, "tx_bytes": 1, "rx_bytes": 1},
        {"seq": 2, "ts_us": 9_000, "type": "step_end", "step": 1,
         "dur_us": 8_000},
    ]
    l = fleet.ledger_from_events(events, rank=3)
    assert l["rank"] == 3
    assert l["window_us"] == 8_000
    assert l["buckets"]["exposed_wire"] == 2_000
    assert l["buckets"]["compute"] == 6_000
    assert sum(l["buckets"].values()) == 8_000


def test_dominant_phase_and_ledger_signals():
    l = {"window_us": 100_000,
         "buckets": {name: 0 for name in fleet.BUCKETS}}
    l["buckets"].update(stall=30_000, compute=20_000, idle=50_000)
    # idle is an absence of evidence, not a phase — stall dominates.
    assert fleet.dominant_phase(l) == "stall"
    sig = fleet.ledger_signals(l)
    assert sig["stall_ms"] == 30.0
    assert sig["queued_idle_share"] == 0.0
    empty = {"window_us": 0,
             "buckets": {name: 0 for name in fleet.BUCKETS}}
    assert fleet.dominant_phase(empty) == ""
    assert fleet.ledger_signals(empty)["queued_idle_share"] == 0.0


# ---- simworld fleet lane ----------------------------------------------


def test_simworld_fleet_analysis_64_ranks(tmp_path):
    """The synthesized fleet with the full r23 evidence surface: every
    rank reconciles exactly, fused-lane waits halve the exposed wire,
    critpath names the straggler, and the recorded breach folds out of
    rank 0's dump once."""
    ranks, steps, slow = 64, 4, 21
    harness.write_sim_step_dumps(
        str(tmp_path), ranks=ranks, steps=steps, slow_rank=slow,
        waits=True, serving=True,
        breach={"objective": 4, "rank": slow, "value": 750, "phase": 6,
                "objective_name": "stall_ms", "phase_name": "stall"})
    a = fleet.analyze(str(tmp_path))
    assert a["ranks"] == list(range(ranks))
    for rank, l in a["per_rank"].items():
        assert sum(l["buckets"].values()) == l["window_us"], rank
        assert l["buckets"]["unattributed"] == 0, rank
        # waits=True: the wait block is half of each span, so exposed
        # wire must be exactly half the raw span measure per step.
        span = 15_000 if rank == slow else 180_000 - 15_000 - 2_000
        assert l["buckets"]["exposed_wire"] == steps * (span // 2), rank
    assert a["fleet"]["worst_rank"] == slow
    assert a["fleet"]["worst_via"] == "critpath"
    assert a["critpath"]["blocking_counts"] == {slow: steps}
    (breach,) = a["slo"]["breach_events"]
    assert breach["source_rank"] == 0
    assert breach["objective"] == "stall_ms"
    assert breach["breach_rank"] == slow
    assert breach["phase"] == "stall"
    # Rendering names the worst rank and the breach.
    text = fleet.format_fleet(a, max_ranks=8)
    assert f"worst rank: {slow} (via critpath)" in text, text
    assert f"breach [stall_ms] rank {slow}" in text, text
    assert "... 56 more ranks" in text, text


def test_simworld_256_rank_aggregation_stays_interactive(tmp_path):
    """The acceptance bar: the 256-rank fleet fold must stay an
    interactive operation (< 2 s; perfwatch watches the same number
    as `analyze_s` of a `fleet_utilization` row)."""
    harness.write_sim_step_dumps(str(tmp_path), ranks=256, steps=4,
                                 slow_rank=85, waits=True, serving=True)
    t0 = time.perf_counter()
    a = fleet.analyze(str(tmp_path))
    dt = time.perf_counter() - t0
    assert dt < 2.0, dt
    assert len(a["ranks"]) == 256
    assert a["fleet"]["worst_rank"] == 85


def test_fused_lane_wait_intersection_in_critpath(tmp_path):
    """The offline/live equivalence satellite: with wait events in the
    dump, critpath's `wire` phase is spans ∩ waits (the ledger's
    exposed measure); without them the raw span union stands."""
    harness.write_sim_step_dumps(str(tmp_path), ranks=2, steps=1,
                                 slow_rank=0, waits=True)
    dump = postmortem.load_blackbox(
        str(tmp_path / "blackbox-rank1.jsonl"))[-1]
    phases = critpath.phase_intervals(dump)
    span = 180_000 - 15_000 - 2_000
    assert critpath.union_measure(phases["wire"]) == span // 2
    assert critpath.union_measure(phases["wait"]) == span // 2
    bare = str(tmp_path / "nowaits")
    harness.write_sim_step_dumps(bare, ranks=2, steps=1, slow_rank=0)
    dump2 = postmortem.load_blackbox(
        os.path.join(bare, "blackbox-rank1.jsonl"))[-1]
    phases2 = critpath.phase_intervals(dump2)
    assert not phases2["wait"]
    assert critpath.union_measure(phases2["wire"]) == span


# ---- SLO grammar / drift / recording ----------------------------------


def test_slo_grammar_rejects_typos_loudly():
    with pytest.raises(ValueError, match="unknown signal"):
        slo.parse("serving_p99 < 250")
    with pytest.raises(ValueError, match="unknown operator"):
        slo.parse("stall_ms <= 500")
    with pytest.raises(ValueError, match="expected"):
        slo.parse("stall_ms<500")
    obj = slo.parse("overlap_efficiency > 0.4")
    assert obj == slo.Objective("overlap_efficiency", ">", 0.4)
    # One ';'-separated string (the --slo / HOROVOD_SLO form).
    objs = slo.parse_all("stall_ms < 500; serving_p99_ms < 2000")
    assert [o.name for o in objs] == ["stall_ms", "serving_p99_ms"]


def test_slo_threshold_operators_per_rank():
    engine = slo.SloEngine(("stall_ms < 500",
                            "overlap_efficiency > 0.4"))
    out = engine.evaluate(
        {0: {"stall_ms": 100.0, "overlap_efficiency": 0.8},
         1: {"stall_ms": 900.0, "overlap_efficiency": 0.2}},
        phases={1: "stall"})
    # Attribution is exact by construction: only rank 1's own signals
    # breached, and each breach names rank 1.
    assert [(b.objective, b.rank, b.phase) for b in out] == [
        ("stall_ms", 1, "stall"), ("overlap_efficiency", 1, "stall")]
    # Missing signals are not judged (train-only rank, no serving p99).
    assert engine.evaluate({2: {}}) == []
    assert engine.breaches == out


def test_slo_drift_warmup_and_frozen_baseline():
    engine = slo.SloEngine(("step_time_ewma_ms drift> 2.0",))
    # Warmup: the first _DRIFT_WARMUP observations are never judged
    # against an empty baseline.
    for _ in range(3):
        assert engine.evaluate({0: {"step_time_ewma_ms": 100.0}}) == []
    # 2.5x the learned baseline breaches...
    (b,) = engine.evaluate({0: {"step_time_ewma_ms": 250.0}})
    assert b.objective == "step_time_ewma_ms" and b.rank == 0
    # ...and the baseline stays frozen during the regression (the
    # perfwatch rule: slow must not become the new normal), so the
    # sustained regression keeps breaching.
    for _ in range(5):
        assert len(engine.evaluate({0: {"step_time_ewma_ms": 250.0}})
                   ) == 1
    # A healthy rank alongside keeps its own independent baseline.
    assert engine.evaluate({1: {"step_time_ewma_ms": 250.0}}) == []


def test_slo_record_encodes_ms_and_permille():
    """record() crosses into the C ring by id: ms objectives record
    rounded ms, ratio objectives permille, phases by BUCKETS index."""
    calls = []

    class _Basics:
        def record_slo(self, objective, rank, value, bucket):
            calls.append((objective, rank, value, bucket))

    engine = slo.SloEngine()
    engine.record(_Basics(), [
        slo.Breach("stall_ms", 3, 1234.4, "stall"),
        slo.Breach("overlap_efficiency", 1, 0.25, "exposed_wire"),
        slo.Breach("serving_p99_ms", 2, 9.0, ""),
    ])
    assert calls == [
        (slo.OBJECTIVES.index("stall_ms"), 3, 1234,
         fleet.BUCKETS.index("stall")),
        (slo.OBJECTIVES.index("overlap_efficiency"), 1, 250,
         fleet.BUCKETS.index("exposed_wire")),
        (slo.OBJECTIVES.index("serving_p99_ms"), 2, 9, -1),
    ]


def test_postmortem_folds_redumped_breach_once():
    """Satellite 4: a process re-dumps its ring tail on every fault, so
    the same (rank, seq) breach reaches the merge repeatedly — the
    post-mortem verdict list must not multiply with the fault count."""
    ev = {"type": "slo_breach", "rank": 0, "seq": 41, "t_ms": 12.5,
          "objective_name": "stall_ms", "breach_rank": 1, "value": 900,
          "phase_name": "stall"}
    other = dict(ev, seq=42, breach_rank=2)
    folded = postmortem._fold_slo_breaches([ev, dict(ev), other])
    assert len(folded) == 2, folded
    assert folded[0] == {"source_rank": 0, "objective": "stall_ms",
                         "breach_rank": 1, "value": 900,
                         "phase": "stall", "t_ms": 12.5}


# ---- live observatory -------------------------------------------------


def test_observatory_endpoint_derivation(monkeypatch):
    monkeypatch.setenv("HOROVOD_DEBUG_PORT", "9400")
    monkeypatch.setenv("HOROVOD_SIZE", "3")
    monkeypatch.setenv("HOROVOD_DEBUG_HOST", "0.0.0.0")
    obs = fleet.FleetObservatory()
    # bind-all is not dialable: derivation substitutes loopback.
    assert obs.resolve_endpoints() == {0: "127.0.0.1:9400",
                                      1: "127.0.0.1:9401",
                                      2: "127.0.0.1:9402"}
    # Ephemeral-port worlds have nothing to derive.
    monkeypatch.setenv("HOROVOD_DEBUG_PORT", "0")
    assert fleet.FleetObservatory().resolve_endpoints() == {}
    explicit = fleet.FleetObservatory(endpoints={5: "10.0.0.1:7000"})
    assert explicit.resolve_endpoints() == {5: "10.0.0.1:7000"}


def test_observatory_tolerates_unreachable_ranks():
    """A fleet view that dies with its sickest rank is useless: dead
    endpoints become error rows, the view still answers."""
    obs = fleet.FleetObservatory(endpoints={0: "127.0.0.1:9",
                                            1: "127.0.0.1:9"},
                                 timeout=0.2)
    view = obs.fleet_json()
    assert view["size"] == 2 and view["reachable"] == 0
    assert all("error" in e for e in view["ranks"].values())
    assert view["fleet"]["utilization"] == 0.0
    assert view["fleet"]["worst_rank"] is None
    # read_fleet_signals consumes the stashed view, never re-polls.
    assert obs.last_view is view
    assert len(obs.history) == 1


def test_maybe_observatory_is_a_process_singleton():
    fleet.reset_observatory()
    try:
        a = fleet.maybe_observatory(None)
        assert fleet.maybe_observatory(None) is a
    finally:
        fleet.reset_observatory()


def test_hvd_slo_env_overrides_default_objectives(monkeypatch):
    monkeypatch.setenv("HOROVOD_SLO", "stall_ms < 100")
    obs = fleet.FleetObservatory()
    assert [f"{o.name} {o.op} {o.threshold:g}"
            for o in obs.engine.objectives] == ["stall_ms < 100"]


# ---- report CLI -------------------------------------------------------


def test_report_cli_fleet(tmp_path, capsys):
    harness.write_sim_step_dumps(str(tmp_path / "dumps"), ranks=4,
                                 steps=2, slow_rank=2, waits=True)
    out_json = str(tmp_path / "fleet.json")
    rc = report.main(["--fleet", "--slo", "stall_ms < 500",
                      str(tmp_path / "dumps"), "-o", out_json])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet: 4 ranks" in out, out
    assert "worst rank: 2 (via critpath)" in out, out
    with open(out_json) as f:
        saved = json.load(f)
    assert saved["slo"]["objectives"] == ["stall_ms < 500"]
    assert saved["fleet"]["worst_rank"] == 2


# ---- perfwatch and its --diff over fleet_utilization rows -------------


def _fleet_row(util, ranks=64, breaches=0, analyze_s=0.1):
    return {"metric": "fleet_utilization", "config": "simworld",
            "ranks": ranks, "steps": 8, "schema": 1,
            "utilization": util, "unattributed_share": 0.0,
            "breaches": breaches, "worst_rank": ranks // 3,
            "analyze_s": analyze_s}


def test_perfwatch_flags_utilization_collapse_at_index(tmp_path):
    rows = [_fleet_row(0.8) for _ in range(10)] \
        + [_fleet_row(0.3) for _ in range(4)]
    series = perfwatch.bench_series(rows)
    key = ("fleet_utilization/simworld/64", "utilization")
    assert series[key] == [0.8] * 10 + [0.3] * 4, sorted(series)
    verdicts = {(v["metric"], v["field"]): v
                for v in perfwatch.watch(series)}
    v = verdicts[key]
    assert v["regressed"] and v["index"] == 10, v
    # breaches growing is watched too (direction up).
    assert perfwatch.field_direction("fleet_utilization",
                                     "breaches") == "up"
    assert perfwatch.field_direction("fleet_utilization",
                                     "analyze_s") == "up"


def test_perfwatch_never_cross_joins_world_sizes():
    """`ranks` is identity: a 64-rank and a 256-rank row interleaved
    must form two series, not one EWMA baseline flagging every
    world-size transition."""
    rows = []
    for _ in range(8):
        rows.append(_fleet_row(0.8, ranks=64))
        rows.append(_fleet_row(0.5, ranks=256))
    series = perfwatch.bench_series(rows)
    assert series[("fleet_utilization/simworld/64", "utilization")] \
        == [0.8] * 8
    assert series[("fleet_utilization/simworld/256", "utilization")] \
        == [0.5] * 8
    assert all(not v["regressed"] for v in perfwatch.watch(series))


def test_perfwatch_diff_over_fleet_rows(tmp_path):
    old = str(tmp_path / "old.json")
    new = str(tmp_path / "new.json")
    with open(old, "w") as f:
        f.write(json.dumps(_fleet_row(0.8, breaches=1)) + "\n")
    with open(new, "w") as f:
        f.write(json.dumps(_fleet_row(0.4, breaches=3)) + "\n")
    lines, worst = perfwatch.diff_rows(old, new)
    text = "\n".join(lines)
    assert "utilization" in text and "-50.0%" in text, text
    assert "breaches" in text, text
    assert worst >= 2.0, worst  # breaches 1 -> 3 is the worst delta
