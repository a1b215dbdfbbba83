"""Elastic serving: prefill/decode disaggregation over the host ring,
and the chaos acceptance — SIGKILL a decode rank mid-trace, every
admitted request completes on the survivors with token-identical
greedy output (docs/serving.md "Elastic behavior").

Two-rank worlds: rank 0 frontend+prefill, rank 1 decode; int8 paged KV
blocks ship over the CRC-framed chunked host ring (one alltoall per
assignment round). The kill test's recovery path is the full r12/r14
machinery: typed ``HorovodPeerFailureError`` at the round boundary ->
in-place 1-rank re-formation -> orphaned requests re-queued and decoded
by the survivor — whose replay must be indistinguishable from a world
where the victim never existed.

Workers live in this importable module (spawn must re-import them —
the r11 gotcha).
"""

import os
import signal

import numpy as np
import pytest

from tests.parallel.test_chaos_matrix import run_chaos

pytestmark = pytest.mark.quick

_N_REQUESTS = 8
_RPS = 120.0
_TRACE_SEED = 9
_KILL_ROUND = 5


def _setup(quantized):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu.models import LlamaConfig, llama_init
    from horovod_tpu.serving.scheduler import poisson_trace

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    trace = poisson_trace(_N_REQUESTS, _RPS, seed=_TRACE_SEED,
                          prompt_len=(4, 10), max_new=(3, 7),
                          vocab_size=cfg.vocab_size)
    return cfg, params, trace


def _make_loop(cfg, params, trace, hook=None, quantized=True):
    from horovod_tpu.serving.service import ServingLoop

    return ServingLoop(params, cfg, trace, block_size=8, n_blocks=64,
                       max_batch=4, max_context=32,
                       quantized=quantized, steps_per_round=2,
                       prefill_per_round=2, round_hook=hook)


def _verify_all(report, cfg, params, trace, quantized):
    """Every request's tokens are the uninterrupted run's. With a
    float pool that is ``llama_generate``, whole; with an int8 pool it
    is an engine of the loop's own pool format that serves the request
    alone and loses nobody (``llama_generate`` keeps float keys and
    values: at a near tie its continuation is another, rid 3's last
    token here, 240 against 67 at logits 2.6081 and 2.6000), and
    ``llama_generate`` still for the prompt and the first token."""
    import jax

    from horovod_tpu.models import llama_generate

    assert report["served"] == len(trace), (
        report["served"], len(trace))
    for req in trace:
        ref = np.asarray(llama_generate(
            params, jax.numpy.asarray(req.prompt[None, :]), cfg,
            req.max_new_tokens))[0]
        got = report["completed"][req.rid]
        if quantized:
            n = len(req.prompt) + 1
            np.testing.assert_array_equal(got[:n], ref[:n],
                                          err_msg=f"rid {req.rid}")
            ref = _make_loop(cfg, params, (),
                             quantized=True).engine.serve_alone(req)
        np.testing.assert_array_equal(got, ref, err_msg=f"rid {req.rid}")


def _disagg_worker(rank, size):
    """No-fault 2-rank disaggregation: every request decodes REMOTELY
    (rank 1) off int8 blocks shipped from rank 0's prefill, and the
    output is still llama_generate's exact tokens (f32 reference —
    quantization must not leak into the greedy path's determinism, see
    test_serving.py's quantized-parity note; this seed decodes
    identically, pinning the shipped-vs-local path equivalence)."""
    from horovod_tpu.common import elastic as hvd_elastic
    from horovod_tpu.common.basics import HorovodBasics

    b = HorovodBasics()
    hvd_elastic.init()
    cfg, params, trace = _setup(quantized=False)
    loop = _make_loop(cfg, params, trace, quantized=False)
    report = loop.run()
    if b.rank() == 0:
        assert report["faults_survived"] == 0, report
        _verify_all(report, cfg, params, trace, quantized=False)
        # Disaggregation really happened: the frontend never decoded.
        assert loop.engine.steps == 0, loop.engine.steps
        # r19 rolling-latency signals live on the frontend.
        sig = loop.signals()
        assert sig["requests_served"] == len(trace), sig
        assert sig["serving_p99_ms"] >= sig["serving_p50_ms"] > 0, sig
    else:
        assert report["served"] > 0, "decode rank served nothing"
    # Request-tracing dump for the cross-rank stitch assertion in the
    # test driver (every rank contributes its view of each rid).
    dump_dir = os.environ.get("REQTRACE_DUMPS")
    if dump_dir:
        from horovod_tpu.telemetry import critpath

        critpath.write_event_dump(
            os.path.join(dump_dir, f"blackbox-rank{b.rank()}.jsonl"),
            b.rank(), b.size(), b.events_drain())
    b.shutdown()
    return "ok"


def test_two_rank_disaggregated_poisson_serves_all(tmp_path):
    dump_dir = str(tmp_path / "reqtrace")
    os.makedirs(dump_dir)
    results = run_chaos(_disagg_worker, 2, victims=(), timeout=240,
                        env={"HOROVOD_WIRE_TIMEOUT_MS": "4000",
                             "HOROVOD_EVENTS": "1",
                             "REQTRACE_DUMPS": dump_dir},
                        expect_sigkill=False)
    assert results == {0: "ok", 1: "ok"}
    # Cross-rank trace stitching on a REAL disaggregated run: every
    # rid's chain reassembles from BOTH ranks' dumps on the anchor-pair
    # wall axis — the frontend contributes queued/prefill/kv_ship, the
    # decode rank contributes decode_wait/decode_active, the chain is
    # gap-free with per-phase sums reconciling exactly, and no request
    # carries a fault_requeue span (nothing faulted).
    from horovod_tpu.telemetry import reqtrace

    chains = reqtrace.stitch(dump_dir)
    assert len(chains) == _N_REQUESTS, sorted(chains)
    for rid, c in sorted(chains.items()):
        assert c["complete"], rid
        assert c["ranks"] == [0, 1], (rid, c["ranks"])
        assert reqtrace.chain_gaps(c) == [], rid
        assert sum(c["phase_us"].values()) == c["wall_us"], rid
        assert "fault_requeue" not in c["phase_us"], (rid, c["phase_us"])
        span_ranks = {s["phase"]: s["rank"] for s in c["spans"]}
        assert span_ranks.get("kv_ship") == 0, (rid, span_ranks)
        assert any(s["phase"] == "decode_active" and s["rank"] == 1
                   for s in c["spans"]), (rid, c["spans"])


def _kill_worker(rank, size):
    from horovod_tpu.common import elastic as hvd_elastic
    from horovod_tpu.common.basics import HorovodBasics

    b = HorovodBasics()
    hvd_elastic.init()
    cfg, params, trace = _setup(quantized=True)

    def hook(loop, round_idx):
        if rank == 1 and round_idx == _KILL_ROUND:
            os.kill(os.getpid(), signal.SIGKILL)

    loop = _make_loop(cfg, params, trace, hook=hook, quantized=True)
    report = loop.run()
    assert b.rank() == 0  # the only survivor reports
    assert report["faults_survived"] >= 1, report
    assert b.size() == 1, b.size()
    _verify_all(report, cfg, params, trace, quantized=True)
    # The survivor genuinely took over decoding.
    assert loop.engine.steps > 0
    el = b.metrics_snapshot()["elastic"]
    assert el["faults_detected"] >= 1, el
    b.shutdown()
    return "ok"


def test_kill_decode_rank_midtrace_completes_on_survivor():
    """The ISSUE acceptance chaos case: SIGKILL the decode rank with
    admitted sequences in flight; the surviving frontend re-forms a
    1-rank world, re-queues the orphans, and serves the WHOLE trace
    token-identically to llama_generate."""
    results = run_chaos(_kill_worker, 2, victims={1}, timeout=240,
                        env={"HOROVOD_WIRE_TIMEOUT_MS": "2000"})
    assert results == {0: "ok"}
