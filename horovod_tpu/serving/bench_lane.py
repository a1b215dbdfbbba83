"""Serving bench lane: sustained tok/s + p50/p99 under a Poisson trace.

``python -m horovod_tpu.serving.bench_lane`` runs the all-in-one
continuous-batching engine (single rank, no wire — the scheduler/paged
-cache/decode-step stack is what's being measured) against a seeded
Poisson arrival trace on a tiny llama config, once per KV block format
(f32 and int8), and prints one schema-stamped JSON row per format —
the ``serving_latency`` family ``perfwatch`` and its ``--diff``
watch (p50/p99 up and sustained_tok_s down are the bad directions;
registered in telemetry/perfwatch.py).

It also emits the ``serving_trace_overhead`` row: the same engine
driven CLOSED-LOOP (all requests submitted up front, no arrival
sleeps — the decode-bound regime where per-step tracing would show)
with request tracing on vs off, best-of-N per mode. The acceptance
bar mirrors the r15 events-overhead criterion: < 2% sustained tok/s
regression with tracing on (``overhead_pct`` is perfwatch-watched, up
= bad).

Substrate-independent (CPU jax): what it prints on the CPU proves the
rows' contract (tests/single/test_serving.py), never a rate.
"""

import json
import sys
import time


def serving_rows(n_requests=24, rps=200.0, seed=7):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np  # noqa: F401  (trace helpers return numpy)

    from horovod_tpu.models import LlamaConfig, llama_init
    from horovod_tpu.serving.scheduler import (
        latency_summary,
        poisson_trace,
    )
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    rows = []
    for name, quantized in (("f32", False), ("int8", True)):
        trace = poisson_trace(n_requests, rps, seed=seed,
                              prompt_len=(4, 24), max_new=(4, 24),
                              vocab_size=cfg.vocab_size)
        eng = DecodeEngine(params, cfg, block_size=8, n_blocks=128,
                           max_batch=8, max_context=64,
                           quantized=quantized)
        # Warm EVERY compiled program off the clock: the prefill
        # recompiles per distinct prompt length (static T) and is
        # shared across formats, so an unwarmed first format would eat
        # all the compiles and skew the f32-vs-int8 comparison.
        seen = set()
        for req in trace:
            if len(req.prompt) not in seen:
                seen.add(len(req.prompt))
                eng.prefill(req)
        eng.submit(trace[0])
        eng.run_until_idle()     # decode program for this format
        eng.scheduler.completed.clear()
        t0 = time.monotonic()
        done_at = {}
        for req in trace:
            # Offered-load replay: submit when the trace clock says so.
            now = time.monotonic() - t0
            if req.arrival_t > now:
                time.sleep(req.arrival_t - now)
            eng.submit(req)
            eng.step()
            for rid in list(eng.scheduler.completed):
                done_at.setdefault(rid, time.monotonic() - t0)
        while eng.scheduler.waiting or eng.scheduler.running:
            eng.step()
            for rid in list(eng.scheduler.completed):
                done_at.setdefault(rid, time.monotonic() - t0)
        wall = time.monotonic() - t0
        lat = latency_summary([
            done_at[r.rid] - r.arrival_t for r in trace])
        gen = sum(len(s.tokens) - len(s.req.prompt)
                  for s in eng.scheduler.completed.values())
        rows.append({
            "metric": "serving_latency",
            "config": name,
            "ranks": 1,
            "arrival_rps": rps,
            "block_size": eng.pool.block_size,
            "requests": n_requests,
            "served": len(eng.scheduler.completed),
            "sustained_tok_s": round(gen / wall, 2),
            "p50_ms": lat["p50_ms"],
            "p99_ms": lat["p99_ms"],
            "evictions": eng.scheduler.evictions,
            "unit": "continuous-batching decode, Poisson trace "
                    f"({rps:.0f} rps offered, tiny llama, CPU, "
                    f"paged KV {name}); sustained tok/s + request "
                    "latency percentiles",
        })
    return rows


def trace_overhead_row(n_requests=16, seed=11, repeats=2):
    """Request-tracing overhead on sustained tok/s: the closed-loop
    decode lane (submit everything, drain the engine) measured with
    the kRequest event stream on vs off. Closed-loop on purpose — the
    Poisson replay's arrival sleeps would hide any per-step cost."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from horovod_tpu.models import LlamaConfig, llama_init
    from horovod_tpu.serving.engine import DecodeEngine
    from horovod_tpu.serving.scheduler import poisson_trace
    from horovod_tpu.telemetry import reqtrace

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    trace = poisson_trace(n_requests, 1000.0, seed=seed,
                          prompt_len=(4, 24), max_new=(4, 24),
                          vocab_size=cfg.vocab_size)

    def run_once():
        eng = DecodeEngine(params, cfg, block_size=8, n_blocks=128,
                           max_batch=8, max_context=64)
        for req in trace:
            eng.submit(req)
        t0 = time.monotonic()
        done = eng.run_until_idle()
        wall = time.monotonic() - t0
        gen = sum(len(t) - len(r.prompt)
                  for r, t in ((req, done[req.rid]) for req in trace))
        return gen / wall

    # Warm every compiled program off the clock (prefill recompiles per
    # prompt length; one full pass covers decode too).
    run_once()
    best = {}
    prior = reqtrace.tracing_enabled()  # restore, don't force-enable:
    # an operator who started with HOROVOD_EVENTS=0 keeps the ring off
    for _ in range(repeats):
        for name, on in (("on", True), ("off", False)):
            reqtrace.set_tracing(on)
            try:
                tok_s = run_once()
            finally:
                reqtrace.set_tracing(prior)
            if name not in best or tok_s > best[name]:
                best[name] = tok_s
    overhead = (best["off"] - best["on"]) / best["off"] * 100.0
    return {
        "metric": "serving_trace_overhead",
        "config": "f32",
        "ranks": 1,
        "requests": n_requests,
        "block_size": 8,
        "tok_s_tracing_on": round(best["on"], 2),
        "tok_s_tracing_off": round(best["off"], 2),
        "overhead_pct": round(overhead, 3),
        "criterion": "overhead_pct < 2 (closed-loop decode, "
                     f"best-of-{repeats}; r15 events bar)",
        "pass": overhead < 2.0,
        "unit": "request-tracing cost on sustained tok/s "
                "(kRequest events on vs off, same engine/trace)",
    }


def main():
    for row in serving_rows():
        print("SERVING_ROW " + json.dumps(row), flush=True)
    print("SERVING_ROW " + json.dumps(trace_overhead_row()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
