"""Two-rank serving chaos smoke: ``make serve-smoke``.

The acceptance drill for the serving lane, one command, no
accelerator: a 2-rank prefill/decode world (rank 0 frontend+prefill,
rank 1 decode) serves a Poisson arrival trace with int8 paged KV
shipped over the CRC-framed host ring — then rank 1 is SIGKILLed
mid-trace, with admitted sequences in flight. Asserts:

1. rank 0 takes the typed peer failure at the round boundary, re-forms
   a 1-rank world in place (r12/r14 elastic), re-queues the dead
   rank's in-flight requests, and EVERY trace request completes on the
   survivor;
2. greedy output is TOKEN-IDENTICAL to the uninterrupted run for every
   request — an engine of the same int8 pool format that serves the
   request alone and loses nobody; prompt and first token are
   ``llama_generate``'s. A request's answer does not depend on whether
   its first home died (the static-shape engine + source-side
   quantization determinism, docs/serving.md);
3. the victim really died by SIGKILL (exit code pins the chaos, not a
   clean shutdown);
4. request-scoped tracing EXPLAINS the latency cliff
   (docs/serving.md "Request lifecycle & tracing"): the survivor's
   event dump stitches into one gap-free span chain per completed rid
   (per-phase sums reconcile to the chain's wall time EXACTLY — the
   r17 standard), the victim's orphaned requests carry a
   ``fault_requeue`` span (and only they do), and
   ``report.py --requests`` renders the tail attribution.
"""

import json
import os
import signal
import subprocess
import sys
import time

N_REQUESTS = 12
ARRIVAL_RPS = 60.0
KILL_ROUND = 6
TRACE_SEED = 5


def _trace(cfg):
    from horovod_tpu.serving.scheduler import poisson_trace

    return poisson_trace(N_REQUESTS, ARRIVAL_RPS, seed=TRACE_SEED,
                         prompt_len=(4, 12), max_new=(3, 8),
                         vocab_size=cfg.vocab_size)


def worker():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from horovod_tpu.common import elastic as hvd_elastic
    from horovod_tpu.common.basics import HorovodBasics
    from horovod_tpu.models import (
        LlamaConfig,
        llama_generate,
        llama_init,
    )
    from horovod_tpu.serving.service import ServingLoop

    rank = int(os.environ["HOROVOD_RANK"])
    b = HorovodBasics()
    hvd_elastic.init()
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    trace = _trace(cfg)

    def hook(loop, round_idx):
        if rank == 1 and round_idx == KILL_ROUND:
            # Die holding in-flight sequences: the survivor must
            # re-queue and finish them.
            os.kill(os.getpid(), signal.SIGKILL)

    geometry = dict(block_size=8, n_blocks=64, max_batch=4,
                    max_context=32, quantized=True, steps_per_round=2,
                    prefill_per_round=2)
    loop = ServingLoop(params, cfg, trace, round_hook=hook, **geometry)
    report = loop.run()
    if b.rank() == 0:
        assert report["faults_survived"] >= 1, report
        assert report["served"] == len(trace), (
            report["served"], len(trace))
        for req in trace:
            n = len(req.prompt) + 1
            head = np.asarray(llama_generate(
                params, jax.numpy.asarray(req.prompt[None, :]), cfg,
                1))[0]
            ref = ServingLoop(params, cfg, (),
                              **geometry).engine.serve_alone(req)
            got = report["completed"][req.rid]
            assert np.array_equal(got[:n], head) and np.array_equal(
                got, ref), (
                f"rid {req.rid}: served tokens diverge from the "
                f"uninterrupted run\n got {got}\n ref {ref}")
        summary = {k: report[k] for k in
                   ("requests", "served", "generated_tokens",
                    "faults_survived", "evictions", "rounds",
                    "sustained_tok_s", "p50_ms", "p99_ms")}
        print("SERVE_SMOKE_OK " + json.dumps(summary), flush=True)
        _verify_request_chains(b, loop, report)
    b.shutdown()
    return 0


def _verify_request_chains(b, loop, report):
    """Acceptance 4: dump the survivor's event ring, stitch the
    per-request span chains, and assert the chaos is EXPLAINED — every
    completed rid's chain is gap-free with per-phase sums reconciling
    to its wall time exactly, and `fault_requeue` spans appear on
    precisely the requests the fault orphaned."""
    from horovod_tpu.telemetry import critpath, reqtrace

    dump_dir = os.environ.get("SERVE_SMOKE_DUMPS")
    if not dump_dir:
        return
    path = os.path.join(dump_dir, f"blackbox-rank{b.rank()}.jsonl")
    critpath.write_event_dump(path, b.rank(), b.size(),
                              b.events_drain(),
                              epoch=int(b.lib.hvdtpu_epoch()))
    chains = reqtrace.stitch(dump_dir)
    for rid in report["completed"]:
        chain = chains.get(int(rid))
        assert chain is not None, f"rid {rid}: no stitched chain"
        assert chain["complete"], f"rid {rid}: no terminal done"
        defects = reqtrace.chain_gaps(chain)
        assert not defects, f"rid {rid}: chain defects {defects}"
        # The exact-reconciliation pin, recomputed independently of
        # the stitcher's construction.
        assert sum(chain["phase_us"].values()) == chain["wall_us"], rid
    fault_rids = {rid for rid, c in chains.items()
                  if c["phase_us"].get("fault_requeue", 0) > 0}
    assert fault_rids == loop.requeued_rids, (
        "fault_requeue attribution does not match the re-queued set",
        sorted(fault_rids), sorted(loop.requeued_rids))
    assert fault_rids, "chaos fired but no request carries a " \
                       "fault_requeue span"
    print("REQTRACE_OK " + json.dumps({
        "chains": len(chains),
        "complete": sum(c["complete"] for c in chains.values()),
        "fault_requeued": sorted(fault_rids),
    }), flush=True)


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    if "--worker" in sys.argv:
        return worker()

    import tempfile

    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    dump_dir = tempfile.mkdtemp(prefix="serve_smoke_reqtrace_")
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": "2",
            "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": "2",
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
            "HOROVOD_CONTROLLER_PORT": str(port),
            "HOROVOD_WIRE_TIMEOUT_MS": "2000",
            "HOROVOD_EVENTS": "1",
            "SERVE_SMOKE_DUMPS": dump_dir,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.serving.serve_smoke",
             "--worker"],
            stdout=subprocess.PIPE if r == 0 else subprocess.DEVNULL,
            stderr=None, text=True, env=env, cwd=repo))
    t0 = time.monotonic()
    out, _ = procs[0].communicate(timeout=600)
    procs[1].wait(timeout=30)
    ok_lines = [ln for ln in out.splitlines()
                if ln.startswith("SERVE_SMOKE_OK")]
    assert procs[0].returncode == 0, f"rank 0 failed:\n{out}"
    assert ok_lines, f"no SERVE_SMOKE_OK line:\n{out}"
    assert procs[1].returncode == -signal.SIGKILL, (
        "victim exited cleanly — the chaos never fired: "
        f"{procs[1].returncode}")
    summary = json.loads(ok_lines[0].split(" ", 1)[1])
    assert summary["faults_survived"] >= 1, summary
    assert summary["served"] == summary["requests"] == N_REQUESTS
    trace_lines = [ln for ln in out.splitlines()
                   if ln.startswith("REQTRACE_OK")]
    assert trace_lines, f"no REQTRACE_OK line:\n{out}"
    reqtrace_summary = json.loads(trace_lines[0].split(" ", 1)[1])
    assert reqtrace_summary["complete"] == N_REQUESTS, reqtrace_summary
    assert reqtrace_summary["fault_requeued"], reqtrace_summary
    # The operator-facing renderer over the same dumps: the tail band
    # must attribute through the CLI too (report.py --requests).
    from horovod_tpu.telemetry.report import main as report_main

    rc = report_main(["--requests", dump_dir])
    assert rc == 0, "report.py --requests failed over smoke dumps"
    print(f"serve-smoke OK in {time.monotonic() - t0:.1f}s: "
          f"{summary['served']}/{summary['requests']} requests "
          f"token-identical across a SIGKILLed decode rank "
          f"({summary['generated_tokens']} tokens, "
          f"p99 {summary['p99_ms']:.0f} ms, "
          f"{summary['faults_survived']} fault(s) survived; "
          f"{reqtrace_summary['complete']} gap-free request chains, "
          f"fault_requeue on {reqtrace_summary['fault_requeued']})")
    # Dumps are forensic evidence on a FAILED run (every assertion
    # above raises before this line, leaving them in place); a green
    # run cleans up after itself instead of leaking a /tmp dir per CI
    # invocation.
    import shutil

    shutil.rmtree(dump_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
