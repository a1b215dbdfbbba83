"""Simulated-world drivers: scaling rows and synthetic black-box dumps.

See the package docstring and docs/scale.md for the methodology; the
native entry is ``hvdtpu_simworld_run`` (csrc/simworld.cc).
"""

import json
import os
import time

from horovod_tpu.common.basics import HorovodBasics

_basics = HorovodBasics()

# The bench ladder (docs/scale.md): small points anchor the curve's
# intercept, 256 is the north-star world size the r12-r15 machinery
# claims to serve.
DEFAULT_WORLD_SIZES = (8, 32, 64, 128, 256)
DEFAULT_TREE_FANOUT = 8


def run_world(ranks, tree_fanout=0, elems=1024, rounds=3, kill_rank=-1,
              kill_round=-1):
    """One simulated world; returns the native JSON report as a dict
    (raises on any non-injected failure). ``tree_fanout=0`` is the
    flat-star baseline, ``>= 2`` the tree gather."""
    return _basics.simworld_run(ranks, tree_fanout=tree_fanout,
                                elems=elems, rounds=rounds,
                                kill_rank=kill_rank,
                                kill_round=kill_round)


def _phase_stats(report, phase):
    h = report.get("phases", {}).get(phase)
    if not h or not h.get("count"):
        return {}
    return {
        "mean_us": h["sum_us"] // h["count"],
        "p50_us": h["p50_us"],
        "p90_us": h["p90_us"],
        "count": h["count"],
    }


def scaling_profile(world_sizes=DEFAULT_WORLD_SIZES,
                    tree_fanout=DEFAULT_TREE_FANOUT, elems=256,
                    rounds=6):
    """The ``control_plane_scaling`` bench rows: for every world size,
    one flat-star row and one tree row — BOTH curves, so the sub-linear
    claim for the tree gather is checkable against its own baseline in
    the same run. Per row: world standup, mean
    negotiation+allreduce round, and the gather/broadcast phase stats
    the curves are drawn from."""
    rows = []
    for ranks in world_sizes:
        for fanout in (0, tree_fanout):
            if fanout and ranks <= fanout + 1:
                continue  # tree degenerates to the star
            t0 = time.monotonic()
            rep = run_world(ranks, tree_fanout=fanout, elems=elems,
                            rounds=rounds)
            rows.append({
                "metric": "control_plane_scaling",
                "config": "flat" if fanout == 0 else f"tree{fanout}",
                "ranks": ranks,
                "rounds": rounds,
                "elems": elems,
                "standup_us": rep.get("standup_us"),
                "round_mean_us": rep.get("round_us", {}).get("mean"),
                "gather": _phase_stats(rep, "gather"),
                "broadcast": _phase_stats(rep, "broadcast"),
                "allreduce_ok": rep.get("allreduce_ok"),
                "wall_s": round(time.monotonic() - t0, 3),
            })
    return rows


# ---- synthetic per-rank black-box dumps -------------------------------
#
# The in-process world shares ONE event ring and ONE process, so real
# per-rank dump FILES cannot come out of it. For the merge-at-scale
# lane we synthesize the fleet's dumps in the exact DumpBlackBox schema
# (csrc/operations.cc): per surviving rank a header (clock anchors +
# fault record) and an event tail whose content mirrors what that rank
# would have recorded — survivors show progress then a fault; the
# coordinator's dump names the dead rank with certainty (probe-sweep
# attribution), everyone else suspects a neighbor (timeout), which is
# exactly the proof-vs-suspicion geometry merge_post_mortem untangles.


def write_sim_step_dumps(out_dir, ranks, steps, slow_rank, step_ms=120,
                         wire_ms=15, slow_ms=60, epoch=0, skew_us=900,
                         waits=False, serving=False, breach=None):
    """Synthesize per-rank STEP-ANATOMY dumps for the critical-path
    merge at fleet scale (the step-window twin of
    :func:`write_sim_dumps`): every rank records the same
    ``step_begin``/``step_end`` windows (one id sequence — the SPMD
    mark contract), but ``slow_rank`` spends ``slow_ms`` extra in
    unrecorded compute each step while everyone else's wire span
    stretches to absorb the wait — exactly the signature a real
    straggler leaves, so ``critpath.critical_path`` must name
    ``slow_rank`` with phase ``compute`` on EVERY step
    (tests/single/test_critpath.py pins this at 64 ranks; r16 gotcha 1
    applies — the in-process simworld cannot emit real per-rank files).

    The r23 fleet lane (docs/fleet.md) rides on three opt-in knobs,
    defaulted off so the critpath geometry above is untouched:

    - ``waits=True`` pairs each wire span with a ``wait`` block ending
      at the same instant but HALF the duration — exposed wire on the
      fused lane is ``spans ∩ waits``, so the rank-seconds ledger must
      book exactly half of each span as ``exposed_wire``;
    - ``serving=True`` runs one request per step through
      queued -> prefill -> decode_active -> done at fixed fractions of
      the window (10%/30%/80%), exercising the serving buckets;
    - ``breach={"objective": ..., "rank": ..., "value": ...,
      "phase": ...}`` records one ``slo_breach`` event on rank 0 (ids
      per the pinned tables — the live observatory's footprint).

    Returns the list of dump paths."""
    os.makedirs(out_dir, exist_ok=True)
    base_unix = int(time.time() * 1e6)
    total_us = (step_ms + slow_ms) * 1000
    wire_us = wire_ms * 1000
    paths = []
    for rank in range(ranks):
        path = os.path.join(out_dir, f"blackbox-rank{rank}.jsonl")
        steady0 = 5_000_000 + rank * 333_007
        unix0 = base_unix + skew_us * rank  # simulated NTP skew
        header = {
            "kind": "blackbox_header", "rank": rank, "size": ranks,
            "epoch": epoch, "unix_us": unix0, "steady_us": steady0,
            "fault": {},
        }
        lines = [json.dumps(header)]
        seq = 0

        def emit(ts, typ, **fields):
            nonlocal seq
            row = {"seq": seq, "ts_us": ts, "type": typ}
            row.update(fields)
            lines.append(json.dumps(row))
            seq += 1

        for k in range(1, steps + 1):
            begin = steady0 + (k - 1) * total_us
            end = begin + total_us
            emit(begin, "step_begin", step=k)
            if serving:
                # One request per step, rid = step: enters queued early,
                # prefills, decodes, and completes inside the window.
                rid = k
                emit(begin + total_us // 10, "request", phase=0,
                     rid=rid, aux=0, phase_name="queued")
                emit(begin + (3 * total_us) // 10, "request", phase=1,
                     rid=rid, aux=0, phase_name="prefill")
                emit(begin + (8 * total_us) // 10, "request", phase=4,
                     rid=rid, aux=0, phase_name="decode_active")
                emit(end - 500, "request", phase=7, rid=rid, aux=0,
                     phase_name="done")
            # The slow rank computes for most of the window and runs a
            # short span at the end; everyone else finishes local work
            # quickly and their span blocks until the slow rank's data
            # arrives (span stamped at its END with dur_us).
            dur = wire_us if rank == slow_rank else \
                total_us - wire_us - 2000
            emit(end - 1000, "wire_span", plane=0, dur_us=dur,
                 tx_bytes=1 << 20, rx_bytes=1 << 20)
            if waits:
                # Fused-lane evidence: the API thread only BLOCKED for
                # the back half of the span.
                emit(end - 1000, "wait", dur_us=dur // 2)
            emit(end, "step_end", step=k, dur_us=total_us)
        if breach is not None and rank == 0:
            emit(steady0 + steps * total_us, "slo_breach",
                 objective=int(breach.get("objective", 0)),
                 breach_rank=int(breach.get("rank", 0)),
                 value=int(breach.get("value", 0)),
                 phase=int(breach.get("phase", 0)),
                 objective_name=breach.get("objective_name", ""),
                 phase_name=breach.get("phase_name", ""))
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        paths.append(path)
    return paths


def write_sim_dumps(out_dir, ranks, fault_rank, events_per_rank=64,
                    epoch=0, skew_us=1500):
    """Write ``ranks - 1`` survivor dumps (the dead rank writes none —
    that absence IS the root-cause evidence) under ``out_dir``;
    returns the list of paths."""
    os.makedirs(out_dir, exist_ok=True)
    base_unix = int(time.time() * 1e6)
    paths = []
    for rank in range(ranks):
        if rank == fault_rank:
            continue
        path = os.path.join(out_dir, f"blackbox-rank{rank}.jsonl")
        # Per-rank steady clocks start at unrelated offsets; the header
        # anchor pair is what lets the merge align them.
        steady0 = 10_000_000 + rank * 777_001
        certain = rank == 0  # coordinator: probe-sweep proof
        named = fault_rank if certain else (rank + 1) % ranks
        fault = {
            "kind": "peer",
            "certain": certain,
            "ranks": [named],
            "detect_ms": 12,
            "reason": f"simworld: peer failure (rank {named})",
        }
        header = {
            "kind": "blackbox_header", "rank": rank, "size": ranks,
            "epoch": epoch, "unix_us": base_unix + skew_us * rank,
            "steady_us": steady0 + events_per_rank * 1000,
            "fault": fault,
        }
        lines = [json.dumps(header)]
        for i in range(events_per_rank):
            ts = steady0 + i * 1000
            if i == events_per_rank - 1:
                ev = {"seq": i, "ts_us": ts, "type": "fault", "kind": 0,
                      "certain": 1 if certain else 0, "epoch": epoch,
                      "fault_rank": named}
            elif i == events_per_rank - 2:
                ev = {"seq": i, "ts_us": ts, "type": "retry_window",
                      "attempt": 1, "window_ms": 250}
            else:
                # The dead rank's neighbors stop seeing progress first:
                # their last wire span lands earlier on the merged axis.
                near_dead = abs(rank - fault_rank) <= 1
                cut = events_per_rank - (8 if near_dead else 4)
                typ = "wire_span" if i < cut else "negotiate_begin"
                ev = {"seq": i, "ts_us": ts, "type": typ}
                if typ == "wire_span":
                    ev.update({"plane": 0, "dur_us": 800,
                               "tx_bytes": 4096, "rx_bytes": 4096})
            lines.append(json.dumps(ev))
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        paths.append(path)
    return paths
