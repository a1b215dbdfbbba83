#!/usr/bin/env python3
"""chipbench — the benchmark's entry point.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``. Everything else
worth reading (device, steps, losses, MFU, cache hits) is on earlier
lines. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

This parent never imports jax: a chip belongs to one process at a time.
A one-chip cell runs in one child (``child.py``); a four-chip cell runs
one child per chip under ``python -m horovod_tpu.runner.launch
--tpu-pod``, and the parent gathers the ranks' result files.

Driven by data: a cell names a configuration
(``configs/<name>.json``, whose ``kind`` picks ``models/<kind>.py``) and
a traffic file (``traffic/<name>.json``, whose ``lane`` picks
``lanes/<lane>.py``); each per-layer quantity has a reader of its own,
``layer_metrics/<name>.py`` (``<name>.lm`` and ``<name>.cnn``, split by
the end-to-end metric they move, share ``<name>.py``). A new cell, configuration or metric is new
files and new entries in ``BENCHMARK.json``; nothing here is edited.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
import child  # noqa: E402  (its helpers; it imports jax only when run)
# A run must end within 360 s (1200 s the first time in a checkout,
# which builds the native core and compiles).
CHILD_LIMIT_S = 1150


def fail(message):
    print(f"chipbench: FAILED: {message}", file=sys.stderr)
    sys.exit(1)


def run_group(cmd, env, limit):
    """Run a process group to its end, its output passed through; kill
    the whole group at the limit or on the way out."""
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, start_new_session=True)
    try:
        return proc.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        return None
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)   # stragglers
        except ProcessLookupError:
            pass
        proc.wait()


def merge(ranks, cell, bench, trace):
    """The ranks' results -> the one line. Ranks run in lockstep, so
    rates and step times are rank 0's; memory is the fullest chip's;
    busy time is averaged over the chips."""
    r0 = ranks[0]
    faults = [f"rank {r['rank']}: {f}" for r in ranks for f in r["faults"]]
    section = "per_layer" if trace else "end_to_end"
    values = dict(r0[section])
    if not trace:
        values["peak_hbm_gb"] = max(r["end_to_end"]["peak_hbm_gb"]
                                    for r in ranks)
        values["setup_s"] = max(r["end_to_end"]["setup_s"] for r in ranks)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in child.metrics_of(bench, section, cell["name"])
               if m["name"] in values}
    device = dict(r0["device"])
    device["memory_peak_bytes"] = max(r["device"]["memory_peak_bytes"]
                                      for r in ranks)
    if trace:
        for k in ("busy_s", "window_s"):
            device[k] = sum(r["device"][k] for r in ranks) / len(ranks)
    line = {"correct": not faults, "attempted": r0["attempted"],
            "failed": max(r["failed"] for r in ranks), "metrics": metrics,
            "device": device}
    if trace and "breakdown" in r0:
        line["breakdown"] = r0["breakdown"]
    if faults:
        line["faults"] = faults
    return line


def main():
    t0 = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench, cell, _, traffic = child.find_cell(args.workload)
    if traffic["ranks"] != cell["chips"]:
        fail(f"{cell['name']}: {cell['chips']} chips but its traffic "
             f"file runs {traffic['ranks']} ranks")
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        fail("the program under test (horovod_tpu/) is not in this "
             "checkout")
    # The native core outlasts a run in the checkout: built once.
    if not os.path.isfile(os.path.join(ROOT, "horovod_tpu", "lib",
                                       "libhvdtpu_core.so")):
        if run_group(["make", "-s", "core"], None, 600) != 0:
            fail("make core")

    out = tempfile.mkdtemp(prefix="chipbench-out-")
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.join(HERE, "child.py"),
             "--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds), "--trace", str(args.trace),
             "--out", out, "--t0", repr(t0)]
    if traffic["ranks"] > 1:
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               "--tpu-pod"] + cmd
    try:
        rc = run_group(cmd, env, CHILD_LIMIT_S)
        if rc is None:
            fail(f"{cell['name']}: killed after {CHILD_LIMIT_S} s")
        if rc != 0:
            fail(f"{cell['name']}: exit code {rc}")
        ranks = []
        for r in range(traffic["ranks"]):
            path = os.path.join(out, f"rank{r}.json")
            if not os.path.isfile(path):
                fail(f"{cell['name']}: rank {r} left no result")
            with open(path) as f:
                ranks.append(json.load(f))
    finally:
        shutil.rmtree(out, ignore_errors=True)
    dev = ranks[0]["device"]
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        fail(f"{cell['name']}: ran on {dev}")
    sys.stdout.flush()
    print(json.dumps(merge(ranks, cell, bench, bool(args.trace))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
