"""Serving lane: paged-allocator invariants, scheduler semantics,
continuous-batching decode parity, and the bench/perfwatch row
contract (docs/serving.md).

The parity standard is the one the elastic re-queue guarantee rests
on: ``DecodeEngine`` output must be TOKEN-IDENTICAL to
``llama_generate`` for every request, regardless of batch composition,
admission order, eviction/replay, or the int8 block format's presence
(quantization error changes logits, but deterministically — the same
request always takes the same path).
"""

import json

import numpy as np
import pytest

import jax

from horovod_tpu.models import LlamaConfig, llama_generate, llama_init
from horovod_tpu.serving.kvcache import (
    OutOfBlocks,
    PagedKVCache,
    quantize_blocks,
)
from horovod_tpu.serving.engine import DecodeEngine
from horovod_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    latency_summary,
    poisson_trace,
)

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _reference(params, cfg, req):
    out = llama_generate(params, jax.numpy.asarray(req.prompt[None, :]),
                         cfg, req.max_new_tokens)
    return np.asarray(out)[0]


# ---- paged allocator invariants --------------------------------------


def test_alloc_free_roundtrip_randomized_ragged():
    """Randomized ragged alloc/free churn: every block handed out is
    unique, accounting reconciles at every step, and a full drain
    returns the pool to pristine."""
    pool = PagedKVCache(2, 2, 4, block_size=4, n_blocks=32)
    rng = np.random.default_rng(0)
    held = []
    for _ in range(300):
        if held and (rng.random() < 0.45 or pool.blocks_free < 5):
            blocks = held.pop(rng.integers(len(held)))
            pool.free(blocks)
        else:
            n = pool.blocks_for(int(rng.integers(1, 18)))
            try:
                blocks = pool.alloc(n)
            except OutOfBlocks:
                assert pool.blocks_free < n
                continue
            held.append(blocks)
        flat = [b for blks in held for b in blks]
        assert len(flat) == len(set(flat)), "block double-owned"
        assert pool.blocks_free + len(flat) == pool.blocks_total
    for blocks in held:
        pool.free(blocks)
    assert pool.blocks_free == pool.blocks_total
    with pytest.raises(ValueError):
        pool.free([0])  # double free must be loud


def test_no_block_leaked_after_evict(tiny):
    """A pool too small for the offered load forces evict/replay;
    afterwards every block is back on the free list and every request
    still completed token-identically (greedy replay determinism)."""
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, block_size=4, n_blocks=6,
                       max_batch=4, max_context=24)
    trace = poisson_trace(5, 1000.0, seed=3, prompt_len=(6, 10),
                          max_new=(4, 7), vocab_size=cfg.vocab_size)
    for r in trace:
        eng.submit(r)
    done = eng.run_until_idle()
    assert eng.scheduler.evictions > 0, "pool never pressured"
    assert eng.pool.blocks_free == eng.pool.blocks_total, "leak"
    assert not eng.pool._allocated
    for r in trace:
        np.testing.assert_array_equal(done[r.rid],
                                      _reference(params, cfg, r))


def test_int8_block_dequant_error_bound():
    """Single-shot quantization (the prefill/wire path) must meet the
    per-(block, layer, head) bound |x - dq| <= scale/2 with
    scale = amax/127; incremental tail-block writes (decode) may
    requantize on scale growth, bounded by one extra quantization
    step."""
    rng = np.random.default_rng(7)
    L, H, T, D, bs = 2, 2, 13, 8, 4
    k = (rng.standard_normal((L, H, T, D)) * 3).astype(np.float32)
    v = (rng.standard_normal((L, H, T, D)) * 0.2).astype(np.float32)
    k_q, v_q, k_s, v_s = quantize_blocks(k, v, bs)
    n = k_q.shape[0]
    s_pad = n * bs
    for q, s, x in ((k_q, k_s, k), (v_q, v_s, v)):
        dq = q.astype(np.float32) * s[..., None, None]
        ref = np.zeros((L, H, s_pad, D), np.float32)
        ref[:, :, :T] = x
        ref = ref.reshape(L, H, n, bs, D).transpose(2, 0, 1, 3, 4)
        err = np.abs(dq - ref)
        bound = s[..., None, None] / 2 + 1e-7
        assert (err <= bound).all(), float((err - bound).max())

    # Incremental decode-style writes into one tail block: error stays
    # within ~one requantization step of the final scale.
    pool = PagedKVCache(L, H, D, block_size=bs, n_blocks=4,
                        quantized=True)
    blocks = pool.alloc(1)
    slots = (rng.standard_normal((bs, L, H, D))
             * np.linspace(0.5, 4.0, bs)[:, None, None, None]
             ).astype(np.float32)  # growing amax: worst requant churn
    for i in range(bs):
        pool.write(blocks, i, slots[i][:, :, None, :],
                   slots[i][:, :, None, :])
    k_g, _, k_sc, _ = pool.gather(blocks)
    dq = k_g.astype(np.float32) * k_sc[..., None]
    ref = slots.transpose(1, 2, 0, 3)  # [L, H, bs, D]
    scale_final = np.abs(ref).max(axis=(-2, -1)) / 127.0
    err = np.abs(dq[:, :, :bs] - ref)
    assert (err <= 2.0 * scale_final[..., None, None] + 1e-7).all()


def test_reused_block_quantizes_like_fresh():
    """A re-allocated block must be SCALE-fresh: `_write_block_q`
    merges against the block's current scale, so a reused block still
    carrying its previous owner's larger scale would quantize the new
    owner's first write under it — different bytes than
    `quantize_blocks` (the wire format), breaking the local-write==wire
    equivalence TIMING-DEPENDENTLY (which block the LIFO free list
    hands back depends on eviction churn; caught as a flaky serve-smoke
    token-identity failure at r19)."""
    pool = PagedKVCache(1, 1, 4, block_size=4, n_blocks=2,
                        quantized=True)
    big = np.full((1, 1, 4, 4), 100.0, np.float32)
    blocks = pool.alloc(1)
    pool.write(blocks, 0, big, big)
    pool.free(blocks)
    small = np.full((1, 1, 4, 4), 1.0, np.float32)
    reused = pool.alloc(1)
    assert reused == blocks  # LIFO hands the stale block straight back
    pool.write(reused, 0, small, small)
    k_q, v_q, k_s, v_s = quantize_blocks(small, small, 4,
                                         quantized=True)
    np.testing.assert_allclose(pool.k_scale[reused[0]], k_s[0])
    np.testing.assert_array_equal(pool.k_pool[reused[0]], k_q[0])
    np.testing.assert_array_equal(pool.v_pool[reused[0]], v_q[0])


def test_quantized_pool_write_matches_wire_format():
    """The local pool write and the wire's quantize_blocks must
    produce byte-identical int8 content for a fresh prompt — the
    determinism the elastic re-queue token-identity pin rests on."""
    rng = np.random.default_rng(11)
    L, H, T, D, bs = 2, 3, 10, 4, 4
    k = rng.standard_normal((L, H, T, D)).astype(np.float32)
    v = rng.standard_normal((L, H, T, D)).astype(np.float32)
    k_q, v_q, k_s, v_s = quantize_blocks(k, v, bs)
    pool = PagedKVCache(L, H, D, block_size=bs, n_blocks=8,
                        quantized=True)
    blocks = pool.alloc(pool.blocks_for(T))
    pool.write(blocks, 0, k, v)
    for i, blk in enumerate(blocks):
        np.testing.assert_array_equal(pool.k_pool[blk], k_q[i])
        np.testing.assert_array_equal(pool.v_pool[blk], v_q[i])
        np.testing.assert_array_equal(pool.k_scale[blk], k_s[i])
        np.testing.assert_array_equal(pool.v_scale[blk], v_s[i])


# ---- scheduler semantics ---------------------------------------------


def test_scheduler_admission_respects_budgets():
    pool = PagedKVCache(1, 1, 4, block_size=4, n_blocks=64)
    sched = ContinuousBatchingScheduler(pool, max_batch=2,
                                        token_budget=30)
    for rid in range(4):
        sched.submit(Request(rid=rid,
                             prompt=np.zeros(10, np.int32),
                             max_new_tokens=4))
    admitted = sched.admit()
    # max_batch caps at 2 even though tokens (11+11=22 <= 30) allow it.
    assert [s.rid for s in admitted] == [0, 1]
    assert sched.queue_depth == 2 and sched.inflight == 2
    # Budget now exhausted for a third 11-token context.
    assert sched.admit() == []
    sig = sched.signals()
    assert sig["serving_queue_depth"] == 2
    assert sig["inflight_sequences"] == 2
    assert sig["kv_blocks_total"] == 64
    assert sig["kv_blocks_free"] == 64 - 2 * pool.blocks_for(11)


def test_scheduler_evict_requeues_front_and_frees():
    pool = PagedKVCache(1, 1, 4, block_size=4, n_blocks=8)
    sched = ContinuousBatchingScheduler(pool, max_batch=4,
                                        token_budget=1000)
    for rid in range(2):
        sched.submit(Request(rid=rid, prompt=np.zeros(8, np.int32),
                             max_new_tokens=4))
    a, b = sched.admit()
    free_before = pool.blocks_free
    sched.evict(b)
    assert pool.blocks_free == free_before + 3  # blocks_for(9) == 3
    assert sched.waiting[0].rid == 1  # front of the line
    assert sched.evictions == 1
    # ensure_slot evicts the youngest OTHER sequence under pressure.
    pool2 = PagedKVCache(1, 1, 4, block_size=4, n_blocks=6)
    sched2 = ContinuousBatchingScheduler(pool2, max_batch=4,
                                         token_budget=1000)
    for rid in range(2):
        sched2.submit(Request(rid=rid,
                              prompt=np.zeros(11, np.int32),
                              max_new_tokens=8))
    s0, s1 = sched2.admit()
    s0.generated = [1]  # cached == 11; next slot crosses into block 4
    while pool2.blocks_for(s0.cached + 1) <= len(s0.blocks):
        s0.generated.append(1)
    assert sched2.ensure_slot(s0)
    assert s1 not in sched2.running, "youngest sibling not evicted"
    assert sched2.waiting and sched2.waiting[0].rid == 1


def test_latency_summary_percentiles():
    lat = latency_summary([0.1] * 98 + [1.0, 2.0])
    assert lat["p50_ms"] == pytest.approx(100.0)
    assert lat["p99_ms"] > 900.0
    assert latency_summary([]) == {"p50_ms": 0.0, "p99_ms": 0.0}


# ---- continuous-batching decode parity --------------------------------


def test_engine_matches_llama_generate_mid_flight_admission(tiny):
    """Requests admitted MID-FLIGHT (while others are half-decoded)
    must still produce llama_generate's exact tokens — the static-
    shape engine's batch-composition independence."""
    cfg, params = tiny
    for quantized in (False, True):
        eng = DecodeEngine(params, cfg, block_size=8, n_blocks=64,
                           max_batch=4, max_context=32,
                           quantized=quantized)
        trace = poisson_trace(6, 1000.0, seed=5, prompt_len=(4, 12),
                              max_new=(3, 8),
                              vocab_size=cfg.vocab_size)
        for r in trace[:3]:
            eng.submit(r)
        for _ in range(2):
            eng.step()
        for r in trace[3:]:
            eng.submit(r)
        done = eng.run_until_idle()
        for r in trace:
            ref = _reference(params, cfg, r)
            if quantized:
                # int8 KV perturbs logits but stays deterministic:
                # prompt + first token (computed pre-quantization)
                # always match, and the continuation is a valid greedy
                # decode (length + dtype pinned).
                np.testing.assert_array_equal(
                    done[r.rid][:len(r.prompt) + 1],
                    ref[:len(r.prompt) + 1])
                assert done[r.rid].shape == ref.shape
            else:
                np.testing.assert_array_equal(done[r.rid], ref)


# ---- bench row + perfwatch registration -------------------------------


@pytest.fixture(scope="module")
def real_rows():
    """ONE real bench-lane run shared by the row-contract tests (a
    tiny offered load keeps the module in the quick lane)."""
    from horovod_tpu.serving.bench_lane import serving_rows

    return serving_rows(n_requests=4, rps=500.0, seed=2)


def test_serving_rows_shape_and_schema(real_rows):
    """The real bench lane emits schema-stampable serving_latency rows
    with the watched fields present."""
    rows = real_rows
    assert [r["config"] for r in rows] == ["f32", "int8"]
    for row in rows:
        assert row["metric"] == "serving_latency"
        assert row["served"] == row["requests"] == 4
        assert row["sustained_tok_s"] > 0
        assert row["p99_ms"] >= row["p50_ms"] >= 0
        for f in ("arrival_rps", "block_size", "ranks"):
            assert f in row, f


def test_perfwatch_watches_serving_rows():
    """The sentinel's registration (field_direction + row identity)
    must flag a p99 regression and a tok/s collapse in serving rows,
    and keep differently-configured traces in separate series."""
    from horovod_tpu.telemetry import perfwatch as pw

    assert pw.field_direction("serving_latency", "p99_ms") == "up"
    assert pw.field_direction("serving_latency", "p50_ms") == "up"
    assert pw.field_direction("serving_latency",
                              "sustained_tok_s") == "down"
    for f in ("arrival_rps", "block_size"):
        assert f in pw.ROW_IDENTITY_FIELDS

    def row(cfg, rps, p99, toks):
        return {"metric": "serving_latency", "config": cfg,
                "arrival_rps": rps, "block_size": 8, "ranks": 1,
                "p99_ms": p99, "sustained_tok_s": toks, "schema": 1}

    rows = [row("f32", 100.0, 50.0, 900.0) for _ in range(6)]
    rows += [row("f32", 100.0, 200.0, 300.0) for _ in range(3)]
    # A second trace config interleaved: must form its OWN series, not
    # perturb the first one's baseline.
    rows += [row("f32", 400.0, 500.0, 900.0) for _ in range(6)]
    series = pw.bench_series(rows)
    keys = {k for k in series}
    assert any(k[1] == "p99_ms" and "100.0" in k[0] for k in keys)
    assert any(k[1] == "p99_ms" and "400.0" in k[0] for k in keys)
    verdicts = pw.watch(series, rel_threshold=0.25, consecutive=2)
    flagged = {(v["metric"], v["field"]) for v in verdicts
               if v["regressed"]}
    assert any(f == "p99_ms" and "100.0" in m for m, f in flagged)
    assert any(f == "sustained_tok_s" and "100.0" in m
               for m, f in flagged)
    assert not any("400.0" in m for m, f in flagged), (
        "steady series flagged — identity grouping broke")


def test_diff_and_perfwatch_on_real_serving_row_files(real_rows,
                                                     tmp_path):
    """The --diff/perfwatch contract on serving rows, exercised from
    two REAL row files (bench-lane output written to disk, with a
    schema stamp):

    - identity separation: rows join strictly on the full identity
      (arrival_rps/block_size included) — a changed block_size makes a
      NEW series/row, it never cross-joins into the old one;
    - a p99 regression between the two files shows in --diff with the
      right sign, and a series built from the same two files flags
      p99_ms through perfwatch at the regressed index.
    """
    import copy

    from horovod_tpu.telemetry import perfwatch as pw

    old_rows = copy.deepcopy(real_rows)
    new_rows = copy.deepcopy(real_rows)
    for r in old_rows + new_rows:
        r.setdefault("schema", 1)
    # Regress the f32 row's p99 3x in the new file; move the int8
    # row's block geometry so it becomes a DIFFERENT identity.
    new_rows[0]["p99_ms"] = old_rows[0]["p99_ms"] * 3.0 + 1.0
    new_rows[1]["block_size"] = 16
    old_path, new_path = tmp_path / "old.json", tmp_path / "new.json"
    old_path.write_text(json.dumps(old_rows))
    new_path.write_text(json.dumps(new_rows))

    lines, worst = pw.diff_rows(str(old_path), str(new_path))
    text = "\n".join(lines)
    f32_p99 = [ln for ln in lines
               if "f32" in ln and "p99_ms" in ln]
    assert f32_p99 and "+" in f32_p99[0], text
    assert worst >= 2.0, worst
    # The re-geometried int8 row did NOT join across block sizes: it
    # appears as only-in on both sides instead of a bogus delta.
    assert sum("(only in" in ln for ln in lines) == 2, text

    # perfwatch over a series drawn from the same two real files:
    # 6 healthy observations then 3 regressed ones.
    series_rows = (pw.load_rows(str(old_path)) * 6
                   + pw.load_rows(str(new_path)) * 3)
    series = pw.bench_series(series_rows)
    verdicts = pw.watch(series, rel_threshold=0.25, consecutive=2)
    flagged = {(v["metric"], v["field"]): v for v in verdicts
               if v["regressed"]}
    p99_flags = [k for k in flagged if k[1] == "p99_ms"
                 and "f32" in k[0]]
    assert p99_flags, (sorted(flagged), verdicts)
    assert flagged[p99_flags[0]]["index"] == 6
    # The int8 series (identity changed mid-stream) split into two
    # short series rather than flagging a phantom regression.
    assert not any("int8" in m for m, f in flagged), sorted(flagged)


def test_trace_overhead_row_shape():
    """The serving_trace_overhead row (the <2% tracing-cost criterion
    the driver's bench gate watches): measured fields present, both
    modes productive, tracing left ON afterwards. The 2% bound itself
    is asserted by the bench criterion field, not here — a loaded CI
    box must not turn a measurement into a flake."""
    from horovod_tpu.serving.bench_lane import trace_overhead_row
    from horovod_tpu.telemetry import perfwatch as pw, reqtrace

    row = trace_overhead_row(n_requests=3, seed=4, repeats=1)
    assert row["metric"] == "serving_trace_overhead"
    assert row["tok_s_tracing_on"] > 0
    assert row["tok_s_tracing_off"] > 0
    assert isinstance(row["pass"], bool)
    assert "overhead_pct" in row and "criterion" in row
    # perfwatch watches the overhead (up = tracing got more expensive).
    assert pw.field_direction("serving_trace_overhead",
                              "overhead_pct") == "up"
    assert reqtrace.tracing_enabled(), "bench left tracing off"


def test_eviction_amplification_counters(tiny):
    """Recomputed-prefill vs useful tokens (docs/serving.md): eviction
    churn moves the recompute counter by exactly the re-prefilled
    prompt lengths, completions move useful tokens, and the signal set
    carries the ratio."""
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, block_size=4, n_blocks=6,
                       max_batch=4, max_context=24)
    trace = poisson_trace(5, 1000.0, seed=3, prompt_len=(6, 10),
                          max_new=(4, 7), vocab_size=cfg.vocab_size)
    for r in trace:
        eng.submit(r)
    done = eng.run_until_idle()
    sched = eng.scheduler
    assert sched.evictions > 0, "pool never pressured"
    assert sched.recomputed_prefill_tokens > 0
    assert sched.useful_tokens == sum(
        len(t) - len(r.prompt) for r, t in
        ((req, done[req.rid]) for req in trace))
    sig = sched.signals()
    assert sig["recomputed_prefill_tokens"] \
        == sched.recomputed_prefill_tokens
    assert sig["useful_tokens"] == sched.useful_tokens
    assert sig["eviction_amplification"] == pytest.approx(
        sched.recomputed_prefill_tokens / sched.useful_tokens,
        abs=1e-5)


# ---- service bookkeeping: fault-safe report delivery ------------------


def _bare_loop(cfg, params, trace=()):
    from horovod_tpu.serving.service import ServingLoop

    # Construction needs no live core — only the engine + bookkeeping.
    return ServingLoop(params, cfg, trace, block_size=8, n_blocks=16,
                       max_batch=2, max_context=32)


def test_done_outbox_resends_until_next_successful_round(tiny):
    """A completion must ride EVERY control message until the round
    AFTER the one that carried it succeeds (receiving the frontend's
    next control is the proof it was processed) — a collective failure
    mid-round must never lose a surviving rank's completions."""
    from horovod_tpu.serving.scheduler import Request, Sequence

    cfg, params = tiny
    loop = _bare_loop(cfg, params)
    seq = Sequence(req=Request(rid=7, prompt=np.zeros(4, np.int32),
                               max_new_tokens=2), generated=[1, 2])
    loop.engine.scheduler.completed[7] = seq
    assert 7 in loop._done_out()
    assert 7 in loop._done_out(), "outbox drained before delivery proof"
    assert loop.served_local == 1, "double-counted on re-send"
    # Round R's allgather succeeded carrying done=[7]: promoted to
    # inflight, still re-sent (the frontend may not have finished R).
    loop._retire_inflight({"acks": [], "rejects": [], "done": [7]})
    assert 7 in loop._done_out()
    # Round R+1 succeeded: the frontend provably applied R -> retired.
    loop._retire_inflight({"acks": [], "rejects": [], "done": [7]})
    assert 7 not in loop._done_outbox
    # A fault resets the proof chain but keeps the outbox.
    loop._done_outbox[9] = [1]
    loop._inflight = {"acks": [], "rejects": [], "done": [9]}
    loop._inflight = {"acks": [], "rejects": [], "done": []}  # _recover
    loop._retire_inflight({"acks": [], "rejects": [], "done": [9]})
    assert 9 in loop._done_outbox, "unconfirmed item retired after fault"


def test_duplicate_completion_cancels_reassigned_copy(tiny):
    """First completion wins: when a rid completes on rank B while its
    re-queued copy runs on rank A, the frontend must cancel A's copy
    (and that cancel must not be wiped before it is transmitted)."""
    from horovod_tpu.serving.scheduler import Request

    cfg, params = tiny
    req = Request(rid=3, prompt=np.zeros(4, np.int32), max_new_tokens=2)
    loop = _bare_loop(cfg, params, [req])
    loop._assigned[3] = {"req": req, "rank": 2, "acked": True}
    loop._apply_decode_report(
        1, {"done": {3: [0, 0, 0, 0, 1, 2]}}, now=1.0)
    assert 3 in loop._completed
    assert 3 in loop._cancel, "reassigned copy never cancelled"
    assert 3 not in loop._assigned


def test_frontend_death_fails_loudly(tiny, monkeypatch):
    """A decode rank must not silently promote itself to frontend
    (it would replay the whole trace against half-decoded state):
    rank 0 in the dead set raises before any re-formation."""
    from horovod_tpu.common import elastic as hvd_elastic

    cfg, params = tiny
    loop = _bare_loop(cfg, params)
    monkeypatch.setattr(hvd_elastic, "survivors", lambda: [1])
    monkeypatch.setattr(
        hvd_elastic, "reset",
        lambda: (_ for _ in ()).throw(AssertionError("reset reached")))
    with pytest.raises(RuntimeError, match="frontend"):
        loop._recover(old_size=2, old_rank=1)


def test_oversize_request_rejected_at_construction(tiny):
    """An oversize request must fail loudly up front, not crash a
    decode rank mid-gather (where it reads as a fault and cascades)."""
    from horovod_tpu.serving.scheduler import Request

    cfg, params = tiny
    big = Request(rid=0, prompt=np.zeros(30, np.int32),
                  max_new_tokens=30)
    with pytest.raises(ValueError, match="max_context"):
        _bare_loop(cfg, params, [big])


# ---- serving signals: /healthz + autoscale back-compat ----------------


def test_serving_signals_defaults_and_live(monkeypatch):
    from horovod_tpu.serving import service as svc
    from horovod_tpu.telemetry.autoscale import SERVING_SIGNAL_DEFAULTS

    # The pinned field set: queue/pool quartet + the r19 rolling
    # latency trio + eviction amplification (docs/serving.md).
    assert svc.serving_signals() == {
        "serving_queue_depth": 0, "inflight_sequences": 0,
        "kv_blocks_free": -1, "kv_blocks_total": -1,
        "serving_p50_ms": 0.0, "serving_p99_ms": 0.0,
        "requests_served": 0, "recomputed_prefill_tokens": 0,
        "useful_tokens": 0, "eviction_amplification": 0.0}
    assert svc.serving_signals() == dict(SERVING_SIGNAL_DEFAULTS)

    class _Stub:
        def signals(self):
            return {"serving_queue_depth": 3, "inflight_sequences": 2,
                    "kv_blocks_free": 10, "kv_blocks_total": 64,
                    "serving_p50_ms": 12.5, "serving_p99_ms": 80.0,
                    "requests_served": 9,
                    "recomputed_prefill_tokens": 40,
                    "useful_tokens": 100,
                    "eviction_amplification": 0.4}

    monkeypatch.setattr(svc, "_live", _Stub())
    assert svc.serving_signals()["serving_queue_depth"] == 3
    assert svc.serving_signals()["kv_blocks_free"] == 10
    assert svc.serving_signals()["serving_p99_ms"] == 80.0
    assert svc.serving_signals()["eviction_amplification"] == 0.4


def test_autoscale_signals_serving_backcompat():
    """Pre-serving observation sources must still construct Signals
    (the r17 defaults discipline), and the policy's decisions must be
    untouched by the new fields."""
    from horovod_tpu.telemetry.autoscale import AutoscalePolicy, Signals

    old = Signals(t=0.0, world_size=2, queue_depth=9)
    new = Signals(t=0.0, world_size=2, queue_depth=9,
                  serving_queue_depth=7, inflight_sequences=3,
                  kv_blocks_free=1, kv_blocks_total=64)
    assert old.serving_queue_depth == 0
    assert old.kv_blocks_free == -1
    # r19 additions (latency trio + amplification) keep the same
    # discipline: defaults construct, decisions untouched.
    assert old.serving_p99_ms == 0.0
    assert old.requests_served == 0
    assert old.eviction_amplification == 0.0
    p_old, p_new = AutoscalePolicy(), AutoscalePolicy()
    d_old = [p_old.decide(Signals(t=float(i), world_size=2,
                                  queue_depth=9)) for i in range(4)]
    d_new = [p_new.decide(Signals(t=float(i), world_size=2,
                                  queue_depth=9, serving_queue_depth=7,
                                  inflight_sequences=3,
                                  kv_blocks_free=1,
                                  kv_blocks_total=64,
                                  serving_p50_ms=50.0,
                                  serving_p99_ms=900.0,
                                  requests_served=123,
                                  recomputed_prefill_tokens=400,
                                  useful_tokens=100,
                                  eviction_amplification=4.0))
             for i in range(4)]
    assert [(d.action, d.target_size) for d in d_old] \
        == [(d.action, d.target_size) for d in d_new]
