"""Drive benchmarks/allreduce_bench.py over a plane × ranks × payload ×
grouping matrix and assemble benchmarks/results_r{N}.json.

Reference analog: the reference's perf story benches NCCL at up to 128
GPUs (``ops/nccl_operations.cc`` scaling claims, docs/benchmarks.rst);
this matrix is its single-box analog: the xla_ici device plane at 1-4
ranks (forced-CPU jax devices when no multi-chip hardware — the same
substrate tests/parallel/test_xla_ici.py uses) plus the host TCP ring,
cold (first negotiation + compile) vs steady state (response-cache
bitvector + executable replay).

Usage: python benchmarks/run_allreduce_matrix.py [--out results.json]
       [--skip-tpu]

Absolute GB/s on a one-core box is scheduler-limited noise for ranks>1
(every rank shares the core); ratios (cold/steady, grouped/flat) and
bus_gbps>0 are the meaningful signals there. The single-rank TPU row
measures real replay latency on the chip.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_case(plane, ranks, size_mb, grouped, op="allreduce", iters=10,
             timeout=600):
    """One launcher run; returns the parsed JSON row or an error row."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if plane == "xla_ici_cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["HOROVOD_XLA_DATA_PLANE"] = "1"
    elif plane == "host_ring":
        env["JAX_PLATFORMS"] = "cpu"
        env["HOROVOD_XLA_DATA_PLANE"] = "0"
    elif plane == "xla_ici_tpu":
        # Pinned, not popped: without a TPU the rank's jax fails at
        # start-up instead of timing the CPU under this plane's name.
        env["JAX_PLATFORMS"] = "tpu"
        env["HOROVOD_XLA_DATA_PLANE"] = "1"
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
           str(ranks), sys.executable,
           os.path.join(ROOT, "benchmarks", "allreduce_bench.py"),
           "--size-mb", str(size_mb), "--iters", str(iters),
           "--op", op]
    if grouped:
        cmd += ["--grouped", str(grouped)]
    t0 = time.time()
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT)
    row = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        # launcher prefixes rank output; the JSON row is rank 0's line
        idx = line.find('{"metric"')
        if idx >= 0:
            try:
                row = json.loads(line[idx:])
            except json.JSONDecodeError:
                pass
    if row is None:
        return {"metric": f"ring_{op}_bandwidth", "op": op,
                "plane": plane,
                "ranks": ranks, "payload_mb": size_mb, "grouped": grouped,
                "error": (proc.stderr or proc.stdout)[-400:],
                "rc": proc.returncode}
    row["plane_config"] = plane
    row["wall_s"] = round(time.time() - t0, 1)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        ROOT, "benchmarks", "results_r05.json"))
    ap.add_argument("--skip-tpu", action="store_true")
    args = ap.parse_args()

    cases = [
        # The headline: device plane at N>1 — fused-program scaling.
        ("xla_ici_cpu", 2, 8, 0, "allreduce"),
        ("xla_ici_cpu", 2, 64, 0, "allreduce"),
        ("xla_ici_cpu", 4, 8, 0, "allreduce"),
        ("xla_ici_cpu", 4, 64, 0, "allreduce"),
        # r5: the full 8-rank timing matrix (r4 proved 8-rank
        # CORRECTNESS only — tests/parallel/test_xla_ici.py).
        ("xla_ici_cpu", 8, 8, 0, "allreduce"),
        ("xla_ici_cpu", 8, 64, 0, "allreduce"),
        ("xla_ici_cpu", 8, 8, 64, "allreduce"),
        # Device-plane Adasum (recursive doubling) + the grouped
        # allgather/reducescatter surfaces, previously unbenched.
        ("xla_ici_cpu", 4, 8, 0, "adasum"),
        ("xla_ici_cpu", 8, 8, 0, "adasum"),
        ("xla_ici_cpu", 8, 8, 16, "allgather"),
        ("xla_ici_cpu", 8, 8, 16, "reducescatter"),
        # 64-tensor fused group through ONE compiled program.
        ("xla_ici_cpu", 2, 8, 64, "allreduce"),
        ("xla_ici_cpu", 4, 8, 64, "allreduce"),
        # Host TCP ring.
        ("host_ring", 2, 8, 0, "allreduce"),
        ("host_ring", 4, 8, 0, "allreduce"),
    ]
    if not args.skip_tpu:
        # Real-chip single-rank replay latency.
        cases += [("xla_ici_tpu", 1, 8, 0, "allreduce"),
                  ("xla_ici_tpu", 1, 64, 0, "allreduce"),
                  ("xla_ici_tpu", 1, 8, 64, "allreduce")]

    rows = []
    for plane, ranks, mb, grouped, op in cases:
        print(f"== {plane} ranks={ranks} {mb}MB grouped={grouped} "
              f"op={op}", file=sys.stderr)
        row = run_case(plane, ranks, mb, grouped, op)
        if "error" in row:
            # One retry: rendezvous port binds occasionally race on a
            # busy box (observed rate ~1/15 launches).
            print("retrying after error", file=sys.stderr)
            row = run_case(plane, ranks, mb, grouped, op)
        print(json.dumps(row), file=sys.stderr)
        rows.append(row)

    out = {
        "note": ("xla_ici_cpu rows run the REAL device data plane "
                 "(negotiation + cached fused XLA programs) on forced-CPU "
                 "jax devices — the no-hardware substrate; on one core, "
                 "absolute GB/s at ranks>1 is scheduler-bound, so read "
                 "cold/steady and grouped ratios, not GB/s. xla_ici_tpu "
                 "rows are the real chip (single rank: replay latency). "
                 "host_ring rows are the native TCP ring."),
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
