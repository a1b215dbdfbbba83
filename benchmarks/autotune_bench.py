"""On-chip proof of the autotuner (HOROVOD_AUTOTUNE=1).

Reference analog: ``horovod/common/parameter_manager.cc`` + autotuning
docs — the reference tunes fusion-buffer size and cycle time online by
scoring realized bytes/sec; ours does the same with a Bayesian
optimizer over the (fusion_threshold, cycle_time) grid
(``csrc/parameter_manager.cc`` + ``csrc/bayes_opt.cc``).

This benchmark runs an EAGER training loop twice in one process:

1. autotune OFF, default knobs — baseline ms/step;
2. shutdown, re-init with ``HOROVOD_AUTOTUNE=1`` +
   ``HOROVOD_AUTOTUNE_LOG`` — run until the optimizer converges (the
   log stops changing knobs), then time steps at the converged
   operating point.

Two lanes:

- default: the GROUPED flagship row (one pre-grouped allreduce/step —
  bench.make_eager_step). r5 proved this a null result: with one
  fused tensor per step the fusion threshold has nothing to fuse.
- ``--ungrouped``: the per-parameter row (bench.
  make_eager_ungrouped_step — 183 small allreduces/step at the 809M
  20-layer geometry), where the fusion buffer and cycle time genuinely
  bind and the tuner has a number to move (VERDICT r5 #4).

Both lanes need a TPU: a step time taken on the CPU is not this metric.

Emits JSON rows and writes ``--out`` (e.g.
``benchmarks/results_r06_autotune.json``) with the warmup->converged
knob trajectory parsed from the autotune log::

    python benchmarks/autotune_bench.py --ungrouped [--out results.json]
"""

import argparse
import csv
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _eager_loop(cfg, batch, seq, steps, warmup, make_step=None):
    """One eager-Horovod training run (bench.make_eager_step — the
    SAME step the eager bench row times — or any other builder, e.g.
    the ungrouped per-grad one); returns mean ms/step over the last
    ``steps`` steps (after ``warmup``)."""
    import numpy as np

    import bench
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import xla_ici

    hvd.init()  # on a TPU, init brings the device plane up or raises
    if not xla_ici.active():
        raise RuntimeError("autotune_bench times the xla_ici device "
                           "plane, which is off")

    data = bench._data(cfg, batch, seq)
    try:
        step, carry, _ = (make_step or bench.make_eager_step)(cfg)
        loss, carry = step(carry, data)
        np.asarray(loss)
        for i in range(warmup):
            loss, carry = step(carry, data)
            if i % 16 == 15:   # bound async run-ahead (HBM)
                np.asarray(loss)
        np.asarray(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, carry = step(carry, data)
        np.asarray(loss)
        dt = (time.perf_counter() - t0) / steps
    finally:
        hvd.shutdown()
    return dt


def _parse_log(path):
    """(trajectory rows, converged knob dict). The tuner logs one CSV
    row per scored window, and on convergence appends a FINAL row at
    the chosen operating point (csrc/parameter_manager.cc), so
    rows[-1] is the knobs the post-convergence steps ran with. Missing
    or empty log -> empty trajectory (the measurements still count)."""
    rows = []
    try:
        with open(path) as f:
            for row in csv.DictReader(f):
                rows.append({
                    "fusion_threshold_bytes":
                        int(row["fusion_threshold_bytes"]),
                    "cycle_time_ms": float(row["cycle_time_ms"]),
                    "score_bytes_per_sec":
                        float(row["score_bytes_per_sec"]),
                })
    except OSError as e:
        print(f"autotune log unreadable ({e}); reporting empty "
              f"trajectory", file=sys.stderr)
    conv = ({"fusion_threshold_bytes":
             rows[-1]["fusion_threshold_bytes"],
             "cycle_time_ms": rows[-1]["cycle_time_ms"]}
            if rows else {})
    return rows, conv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--steps", type=int, default=15)
    # The tuner scores one window per <=5 s of wall time and converges
    # after 20 samples (HOROVOD_AUTOTUNE_STEPS), so the tuning phase
    # needs ~20 x 5 s / step-time steps before the timed window.
    ap.add_argument("--tune-steps", type=int, default=200)
    ap.add_argument("--ungrouped", action="store_true",
                    help="per-parameter allreduces (183 small tensors/"
                         "step) instead of one grouped tree — the "
                         "workload where fusion/cycle knobs bind")
    # Scored windows before the tuner fixes its knobs
    # (HOROVOD_AUTOTUNE_STEPS; core default 20). The ungrouped lane's
    # windows span ~one step each (kMinWindowBytes closes fast on many
    # small tensors), so per-window scores are noisy and the Bayesian
    # optimizer wants more samples than the grouped lane needed.
    ap.add_argument("--autotune-steps", type=int, default=None)
    args = ap.parse_args()

    import bench

    bench.require_tpu("autotune_bench")
    bench.enable_compile_cache()

    if args.ungrouped:
        make_step = bench.make_eager_ungrouped_step
        cfg = bench._same_size_cfg("bfloat16")   # 809M, 20 layers
        batch, seq = 4, 2048
        lane = "ungrouped-per-grad 809M"
        # Bursty per-grad traffic needs score windows spanning SEVERAL
        # steps (one gradient tree of bytes per step), or per-window
        # bytes/sec is dominated by where the window boundary lands in
        # the compute/allreduce burst cycle — set the floor to ~6 steps
        # of gradient bytes.
        import jax as _jax

        from horovod_tpu.models import llama_init
        shapes = _jax.eval_shape(
            lambda k: llama_init(cfg, k), _jax.random.PRNGKey(0))
        step_bytes = sum(x.size * x.dtype.itemsize
                         for x in _jax.tree.leaves(shapes))
        os.environ["HOROVOD_AUTOTUNE_WINDOW_BYTES"] = str(6 * step_bytes)
        os.environ["HOROVOD_AUTOTUNE_WINDOW_CYCLES"] = "40"
    else:
        make_step = None
        cfg = bench._flagship_cfg()
        batch, seq = 4, 2048
        lane = "grouped flagship"

    log_path = "/tmp/hvdtpu_autotune.csv"

    for k in ("HOROVOD_AUTOTUNE", "HOROVOD_AUTOTUNE_LOG"):
        os.environ.pop(k, None)
    dt_off = _eager_loop(cfg, batch, seq, args.steps, warmup=3,
                         make_step=make_step)

    os.environ["HOROVOD_AUTOTUNE"] = "1"
    os.environ["HOROVOD_AUTOTUNE_LOG"] = log_path
    if args.autotune_steps:
        os.environ["HOROVOD_AUTOTUNE_STEPS"] = str(args.autotune_steps)
    try:
        dt_on = _eager_loop(cfg, batch, seq, args.steps,
                            warmup=args.tune_steps, make_step=make_step)
    finally:
        for k in ("HOROVOD_AUTOTUNE", "HOROVOD_AUTOTUNE_LOG",
                  "HOROVOD_AUTOTUNE_STEPS",
                  "HOROVOD_AUTOTUNE_WINDOW_BYTES",
                  "HOROVOD_AUTOTUNE_WINDOW_CYCLES"):
            os.environ.pop(k, None)

    trajectory, converged = _parse_log(log_path)
    row = {
        "metric": "autotune_eager_step_ms",
        "value": round(dt_on * 1e3, 2),
        "unit": (f"ms/step eager {lane} at converged knobs "
                 f"(default knobs: {dt_off * 1e3:.2f} ms/step; "
                 f"converged: {converged}; "
                 f"{len(trajectory)} scored windows, "
                 f"{jax.devices()[0].device_kind})"),
        "vs_baseline": round(dt_off / dt_on, 4),
    }
    print(json.dumps(row), flush=True)
    if args.out:
        payload = {
            "note": f"HOROVOD_AUTOTUNE=1 over the eager {lane} "
                    "training loop (size-1 data plane: the knobs "
                    "govern the core's enqueue->negotiate->fuse "
                    "control path). vs_baseline = default-knob step "
                    "time / converged-knob step time (>1 means the "
                    "tuner helped). Trajectory = every scored "
                    "(fusion, cycle, bytes/sec) window from "
                    "HOROVOD_AUTOTUNE_LOG, in order.",
            "lane": lane,
            "substrate": str(jax.devices()[0].device_kind),
            "default_step_ms": round(dt_off * 1e3, 2),
            "converged_step_ms": round(dt_on * 1e3, 2),
            "converged_knobs": converged,
            "trajectory": trajectory,
            "rows": [row],
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
