"""The process that holds the chip: set-up, the measured window, the
checks, one result file. Started by ``run.py`` (one child for a one-chip
cell; one per rank under ``horovodrun --tpu-pod`` for a four-chip cell).

How a run measures, so that the device stays fed: after warm-up, enqueue
step k, then block on the loss of step k-1 and stamp the clock. One step
is always queued behind the one being waited for; nothing blocks before
the next step is enqueued. The window opens on a full pipeline (two
priming steps come first and count as set-up) and holds n whole steps.
The number of steps is fixed before the window from a short calibration (``--seconds`` / step time, rank 0's
count on every rank), so a run is a fixed amount of work and ranks never
disagree about when to stop.
"""

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
HOST_SPANS = ("enqueue_step", "grad", "allreduce", "apply", "sync")


def load_file(kind, name):
    """chipbench/<kind>/<name>.py as a module: how a model kind, a lane
    or a per-layer metric is found by the name the data files give."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric):
    """The reader of a per-layer metric: ``layer_metrics/<name>.py``, or
    that of the name without its last ``.suffix`` where a quantity is
    split by the end-to-end metric it moves (``device_idle_pct.lm`` and
    ``.cnn`` are both read by ``device_idle_pct.py``)."""
    name = metric
    while not os.path.isfile(os.path.join(HERE, "layer_metrics",
                                          name + ".py")) and "." in name:
        name = name.rsplit(".", 1)[0]
    return load_file("layer_metrics", name)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(workload):
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (have: {sorted(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(files[cell["config"]])
    traffic = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def metrics_of(bench, section, workload):
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def key_of(seed):
    """Any whole number up to a little over 2**31 (more than 32 signed
    bits hold) -> a PRNG key."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def p90(values):
    """90th percentile, linear interpolation between closest ranks."""
    return statistics.quantiles(values, n=10, method="inclusive")[8]


class Listeners:
    """Counts of what jax compiled or fetched from its cache."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _secs(self, name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def cache(self):
        return {"hits": self.hits, "misses": self.misses}


def peak_bytes(device):
    """What the chip held at its fullest, by the allocator's account:
    its peak in use (parameters, optimizer state, gradients, batch) plus
    its peak reserved, where it books a program's temporaries. The two
    peaks need not fall in the same instant: an upper bound, and the
    same one in every run."""
    s = device.memory_stats() or {}
    return int(s.get("peak_bytes_in_use", 0)) + int(
        s.get("peak_bytes_reserved", s.get("bytes_reserved", 0)))


def measure(model, lane, traffic, *, seed, seconds, trace, t0, say,
            listeners=None, layer_metrics=(), trace_keep=None):
    """Warm up, calibrate, run the window, check. Returns the result
    object of ONE process (``run.py`` merges the ranks'). ``model`` is a
    model adapter, ``lane`` a started lane; both come as arguments, so a
    test can pass tiny ones."""
    import jax

    dev = jax.local_devices()[0]
    on_tpu = dev.platform == "tpu"
    jit_kwargs = {"compiler_options": model.compiler_options} \
        if on_tpu and model.compiler_options else {}
    step, carry, batch, lowered = lane.build(model, key_of(seed),
                                             jit_kwargs)
    faults = []
    fault = model.check_lowering(lowered, on_tpu)
    if fault:
        faults.append(fault)
    del lowered
    grad_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(lane.params_of(carry)))
    say(event="built", units_per_step=model.units_per_step,
        unit=model.unit, grad_bytes=grad_bytes,
        seconds_since_start=time.time() - t0)

    # Warm-up: every program this cell uses, twice or more, so that the
    # carry's signature after a step is the one the window sees.
    for _ in range(traffic["warmup_steps"]):
        loss, carry = step(carry, batch)
    jax.block_until_ready((loss, carry))
    first_loss = float(loss)
    # Calibration, pipelined as the window is.
    c0, prev = time.perf_counter(), None
    for _ in range(traffic["calibration_steps"]):
        loss, carry = step(carry, batch)
        if prev is not None:
            prev.block_until_ready()
        prev = loss
    jax.block_until_ready((loss, carry))
    step_s = (time.perf_counter() - c0) / traffic["calibration_steps"]
    traced = traffic["traced_steps"] if trace else 0
    n = max(int(math.ceil(seconds / step_s)), 3, traced + 6)
    n = lane.agree(n)
    trace_at = max(2, min(n // 3, n - traced - 3)) if trace else None
    say(event="calibrated", step_ms=step_s * 1e3, steps=n,
        cache=listeners.cache() if listeners else None)

    compiles_before = listeners.compiles if listeners else 0
    counters_before = lane.counters()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    # Two priming steps refill the pipeline that calibration and agree()
    # drained (and let ranks fall back into lockstep): still set-up. The
    # window opens when the second completes, with step 0 queued behind
    # it, and holds steps 0..n-1 whole.
    priming = 2
    losses, stamps, prev, tracing = [], [], None, False
    # Pauses of the host's garbage collector, by the interval they fell
    # in: a long step with no pause beside it is not this process's.
    gc_pauses, gc_began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_began[0] = time.perf_counter()
        elif stamps:
            ms = (time.perf_counter() - gc_began[0]) * 1e3
            if ms >= 1.0:
                gc_pauses.append([len(stamps) - 1, info["generation"],
                                  round(ms, 1)])

    gc.callbacks.append(on_gc)
    for k in range(-priming, n):
        if k == trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        loss, carry = step(carry, batch)           # enqueue step k
        if prev is not None:
            with jax.profiler.TraceAnnotation("sync"):
                prev.block_until_ready()           # step k-1 is done
            if k > 0:
                stamps.append(time.perf_counter())
            elif k == 0:                           # the window opens
                setup_s = time.time() - t0
                stamps.append(time.perf_counter())
        prev = loss
        if k >= 0:
            losses.append(loss)
        if tracing and k == trace_at + traced + 1:
            prev.block_until_ready()
            jax.profiler.stop_trace()
            tracing = False
    prev.block_until_ready()
    stamps.append(time.perf_counter())
    gc.callbacks.remove(on_gc)
    jax.block_until_ready(carry)
    window_s = stamps[-1] - stamps[0]
    counters_after = lane.counters()
    compiled = (listeners.compiles - compiles_before) if listeners else 0
    peak = peak_bytes(dev)

    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not math.isfinite(x))
    if failed:
        faults.append(f"{failed} steps with a non-finite loss")
    elif not losses[-1] < first_loss:
        faults.append(f"loss did not fall on the fixed batch: "
                      f"{first_loss} -> {losses[-1]}")
    if compiled:
        faults.append(f"{compiled} compilations inside the window")

    # Stamps: the window's opening, then the completion of each of its
    # steps; n intervals, one a step, and the tail is the tail of all.
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    step_ms_p90 = p90(gaps) * 1e3
    units_per_s = len(gaps) / window_s * model.units_per_step * lane.size
    say(event="window", steps=n, intervals=len(gaps), window_s=window_s,
        step_ms_median=statistics.median(gaps) * 1e3,
        step_ms_p90=step_ms_p90,
        step_ms_max=max(gaps) * 1e3, units_per_s=units_per_s,
        first_loss=first_loss, last_loss=losses[-1],
        compiled_in_window=compiled,
        cache=listeners.cache() if listeners else None,
        step_ms_all=[round(g * 1e3, 3) for g in gaps],
        gc_pauses_step_generation_ms=gc_pauses,
        memory_stats=dev.memory_stats())

    params = lane.params_of(carry)
    del carry, loss, prev
    faults += lane.check((counters_before, counters_after), n + priming,
                         grad_bytes, params)
    faults += model.check_outputs(params, key_of(seed + 1), say)

    end_to_end = {
        f"{model.unit}_per_s": units_per_s,
        "step_ms_p90": step_ms_p90,
        "peak_hbm_gb": peak / 1e9,
        "setup_s": setup_s,
    }
    result = {"rank": lane.rank, "size": lane.size, "faults": faults,
              "attempted": n, "failed": failed,
              "end_to_end": end_to_end, "per_layer": {},
              "device": {"platform": dev.platform,
                         "kind": dev.device_kind,
                         "count": jax.device_count(),
                         "memory_peak_bytes": peak}}
    if trace:
        try:
            _reduce_trace(result, trace_dir, trace_keep, model, lane,
                          traffic, layer_metrics,
                          (counters_before, counters_after), n + priming,
                          say)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def _reduce_trace(result, trace_dir, keep, model, lane, traffic,
                  layer_metrics, counters, steps, say):
    from chipbench import xplane

    path = xplane.find(trace_dir)
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, f"rank{lane.rank}.xplane.pb"))
    profile = xplane.load(path)
    # This process's chip: under a pod launch a rank sees one plane.
    chips = [c for c in xplane.chips(profile) if c.busy_ns > 0]
    if not chips:
        raise SystemExit("chipbench: the trace holds no device plane on "
                         "which an operation ran")
    chip = max(chips, key=lambda c: c.busy_ns)
    spans = xplane.host_spans(profile, HOST_SPANS)
    result["device"]["busy_s"] = chip.busy_ns / 1e9
    result["device"]["window_s"] = chip.window_ns / 1e9
    by_name = chip.self_ns_by(xplane.short_name)
    result["breakdown"] = {
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": xplane.name_gaps(chip.idle_gaps(), spans)}
    ctx = types.SimpleNamespace(
        chip=chip, profile=profile, host_spans=spans, counters=counters,
        steps_in_window=steps, traffic=traffic, model=model, lane=lane)
    for name in layer_metrics:
        value = load_reader(name).read(ctx)
        if value is not None:
            result["per_layer"][name] = float(value)
    say(event="trace", chip=chip.name, anchor=chip.anchor,
        traced_steps=chip.steps, window_ms=chip.window_ns / 1e6,
        busy_ms=chip.busy_ns / 1e6,
        by_opcode_ms={k: v / 1e6 for k, v in sorted(
            chip.self_ns_by(xplane.opcode).items(),
            key=lambda kv: -kv[1])[:12]})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    bench, cell, config, traffic = find_cell(args.workload)
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    listeners = Listeners()
    lane = load_file("lanes", traffic["lane"]).Lane(traffic)
    lane.start()   # before the backend is touched
    import jax

    dev = jax.local_devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}

    def say(**fields):
        print(json.dumps({"workload": args.workload, "rank": lane.rank,
                          "device": device, **fields}), flush=True)

    if dev.platform != "tpu":
        raise SystemExit(f"chipbench: {args.workload} needs a TPU; jax "
                         f"found platform {dev.platform!r} "
                         f"({dev.device_kind})")
    if jax.device_count() < cell["chips"]:
        raise SystemExit(f"chipbench: {args.workload} needs "
                         f"{cell['chips']} chips; jax found "
                         f"{jax.device_count()}")
    from chipbench import peaks

    peaks.peak(dev.device_kind)   # an unknown kind is an error
    say(event="start", cache_dir=cache_dir,
        seconds_since_start=time.time() - args.t0)
    model = load_file("models", config["kind"]).Model(config, traffic)
    section = "per_layer" if args.trace else "end_to_end"
    names = [m["name"] for m in metrics_of(bench, section, args.workload)]
    result = measure(
        model, lane, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=args.t0, say=say, listeners=listeners,
        layer_metrics=names if args.trace else (),
        trace_keep=os.environ.get("CHIPBENCH_KEEP_TRACE"))
    units_per_s = result["end_to_end"][f"{model.unit}_per_s"]
    say(event="derived", flops_per_unit=model.flops_per_unit(),
        mfu=peaks.mfu(units_per_s, model.flops_per_unit(),
                      dev.device_kind, lane.size),
        note="required FLOPs (chipbench/peaks.py) over the published "
             "peak; a cut-down depth makes head, embedding and host a "
             "larger share than in a deployment",
        faults=result["faults"], cache=listeners.cache())
    tmp = os.path.join(args.out, f".rank{lane.rank}.json")
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(args.out, f"rank{lane.rank}.json"))
    lane.close()


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # noqa: BLE001 — leaves as an exit code
        # Leave NOW: a failed rank that lingers in teardown keeps its
        # peers, and their chips, waiting until the time limit.
        if isinstance(e, SystemExit) and e.code in (0, None):
            raise
        if isinstance(e, SystemExit):
            print(e, file=sys.stderr)
        else:
            traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
