"""Telemetry subsystem: core metrics snapshot, StepTimer accounting,
static byte prediction, and the cross-rank trace merge.

Pins the ISSUE-4 acceptance bars: (1) hvd.metrics() reconciles with the
``analysis/extract`` jaxpr-walker byte prediction within 1% on a dryrun
eager train step; (2) ``telemetry.report`` merges synthetic multi-rank
timelines into one Perfetto-loadable trace with a per-rank straggler
table that names the right straggler.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import telemetry
from horovod_tpu.telemetry import predict, report

# Part of the sub-5-minute CI lane (make test-quick).
pytestmark = pytest.mark.quick


@pytest.fixture()
def hvd_core(monkeypatch):
    for k in ("HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
              "HOROVOD_LOCAL_SIZE"):
        monkeypatch.delenv(k, raising=False)
    from horovod_tpu.common import basics

    b = basics.HorovodBasics()
    b.init()
    yield b
    b.shutdown()


# ---- snapshot shape & monotonicity ------------------------------------


def test_snapshot_before_init_is_valid():
    snap = telemetry.snapshot()
    assert isinstance(snap, dict)
    assert "ops" in snap and "cycle" in snap and "cache" in snap
    # json-roundtrippable (the C side builds the string by hand)
    json.loads(json.dumps(snap))


def test_fully_populated_snapshot_roundtrips_untruncated(hvd_core):
    """The Append buffer grows dynamically (it was a fixed 768-byte
    stack buffer grown by hand every time a section gained rows —
    truncation silently corrupted the JSON): a snapshot with EVERY
    section populated must parse and keep its final key."""
    from horovod_tpu.common import eager_ops as ops

    # Populate every op class the single-rank ring can execute.
    x = np.arange(64, dtype=np.float32)
    ops.allreduce_async(x, "full.ar").synchronize()
    ops.allgather_async(x, "full.ag").synchronize()
    ops.broadcast_async(x, 0, "full.bc").synchronize()
    snap = hvd_core.metrics_snapshot()
    # Every section present...
    for key in ("ops", "device_ops", "negotiation_us", "queue_us",
                "wire_us", "fusion", "cycle", "cache", "straggler",
                "wire", "elastic", "errors", "knobs"):
        assert key in snap, key
    # ...including the self-healing rows and the new knob columns.
    el = snap["elastic"]
    for key in ("heals", "retries", "crc_errors", "ranks_rejoined",
                "ranks_blacklisted", "detect_us"):
        assert key in el, key
    for key in ("wire_retry_attempts", "wire_retry_backoff_ms",
                "wire_crc", "wire_timeout_ms", "cross_plane"):
        assert key in snap["knobs"], key
    # Truncation would cut the TAIL: knobs is the last section, and the
    # raw JSON must end exactly where the parser says it does.
    raw_len = hvd_core.lib.hvdtpu_metrics_snapshot(None, 0)
    import ctypes

    buf = ctypes.create_string_buffer(int(raw_len) + 512)
    hvd_core.lib.hvdtpu_metrics_snapshot(buf, int(raw_len) + 512)
    raw = buf.value.decode()
    assert raw.endswith("}"), raw[-40:]
    assert json.loads(raw)["knobs"]["cross_plane"] in (
        "auto", "ici", "ring", "hier")


def test_counters_monotonic_and_exact_on_eager_path(hvd_core):
    """Counter monotonicity + exact byte accounting: every allreduce
    adds its payload to ops.allreduce.bytes and nothing ever goes
    backwards."""
    from horovod_tpu.common import eager_ops as ops

    telemetry.metrics_reset()
    prev = telemetry.snapshot()
    assert prev["ops"].get("allreduce", {}).get("bytes", 0) == 0
    total = 0
    for step in range(3):
        for i, n in enumerate((64, 256, 1024)):
            h = ops.allreduce_async(np.ones(n, np.float32),
                                    f"mono.{i}")
            h.synchronize()
            total += n * 4
        snap = telemetry.snapshot()
        ar = snap["ops"]["allreduce"]
        assert ar["bytes"] == total
        assert ar["tensors"] == (step + 1) * 3
        # monotonic across every counter family we diff in production
        assert ar["bytes"] >= prev["ops"].get(
            "allreduce", {}).get("bytes", 0)
        assert snap["cycle"]["count"] >= prev["cycle"]["count"]
        assert (snap["queue_us"]["count"]
                >= prev["queue_us"]["count"])
        prev = snap
    assert prev["queue_us"]["count"] == 9
    assert prev["wire_us"]["count"] > 0


def _mlp_loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)


def _mlp_data():
    k = jax.random.PRNGKey(0)
    params = {"w1": jnp.ones((16, 32), jnp.float32),
              "w2": jnp.ones((32, 4), jnp.float32)}
    batch = {"x": jax.random.normal(k, (8, 16), jnp.float32),
             "y": jnp.zeros((8, 4), jnp.float32)}
    return params, batch


def test_eager_reconciliation_within_1pct(hvd_core):
    """ISSUE-4 acceptance: a dryrun eager train step's measured
    collective bytes (hvd.metrics() deltas) reconcile with the
    analysis/extract jaxpr-walker prediction within 1%."""
    from horovod_tpu.common import eager_ops as ops

    params, batch = _mlp_data()
    predicted = predict.eager_allreduce_bytes(_mlp_loss, params, batch)
    # The walker-based predictor and the walker-free eval_shape
    # cross-check must agree exactly (same grad tree).
    assert predicted == predict.grad_tree_bytes(_mlp_loss, params, batch)

    grads = jax.grad(_mlp_loss)(params, batch)
    before = telemetry.total_collective_bytes()
    steps = 3
    for step in range(steps):
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        handles = [
            ops.allreduce_async(np.asarray(leaf), f"recon.{i}")
            for i, (_, leaf) in enumerate(flat)
        ]
        for h in handles:
            h.synchronize()
    measured = (telemetry.total_collective_bytes() - before) / steps
    assert predicted > 0
    assert abs(measured - predicted) / predicted < 0.01, (
        measured, predicted)


def test_spmd_predictor_uses_walker():
    """collective_bytes walks psums inside jit/scan like the linter
    does: loop-expanded volumes, no devices needed."""
    def fn(x):
        def body(c, _):
            return c + jax.lax.psum(x, "dp"), None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    x = jax.ShapeDtypeStruct((128,), jnp.float32)
    got = predict.collective_bytes(fn, x, axis_env=[("dp", 8)])
    assert got == 4 * 128 * 4  # 4 loop iterations x 128 f32


# ---- StepTimer ---------------------------------------------------------


def test_step_timer_mfu_known_flops():
    """MFU math on a known-FLOPs program: mfu = flops / (dt * peak)."""
    timer = telemetry.StepTimer(flops_per_step=2e9, peak_flops=1e12)
    timer.step_times = [0.5, 0.004, 0.004]  # first = compile, dropped
    assert timer.mean_step_s() == pytest.approx(0.004)
    assert timer.mfu() == pytest.approx(2e9 / 0.004 / 1e12)
    # 2 GFLOP in 4 ms on a 1 TFLOP/s part = 0.5 MFU
    assert timer.mfu() == pytest.approx(0.5)


def test_step_timer_flops_from_compiled_cost_analysis():
    """flops_per_step sourced from lowered.compile().cost_analysis()
    on a program whose FLOPs are known analytically: an (n,n)x(n,n)
    matmul is 2n^3."""
    n = 64
    fn = jax.jit(lambda a, b: a @ b)
    compiled = fn.lower(jnp.ones((n, n)), jnp.ones((n, n))).compile()
    timer = telemetry.StepTimer(peak_flops=1e12)
    flops = timer.add_flops_from_compiled(compiled)
    if flops is None:
        pytest.skip("backend reports no cost analysis flops")
    assert timer.flops_per_step == pytest.approx(2 * n ** 3, rel=0.2)


def test_step_timer_wraps_split_train_step():
    import optax

    from horovod_tpu.parallel.train_step import make_split_train_step

    params, batch = _mlp_data()
    timer = telemetry.StepTimer(peak_flops=1e12)
    ts = make_split_train_step(_mlp_loss, optax.adam(1e-2),
                               microbatches=2, telemetry=timer)
    carry = ts.init(params)
    for _ in range(3):
        loss, carry = ts.step(carry, batch)
    assert timer.steps == 3
    assert timer.mean_step_s() > 0
    # cost-analysis registration happened on the first call (CPU
    # reports flops); grad x2 microbatches + apply are all counted
    assert timer.flops_per_step is None or timer.flops_per_step > 0
    row = timer.summary()
    assert row["steps"] == 3


def test_step_timer_telemetry_does_not_change_jaxpr():
    """The instrumented step must trace to the SAME program as the
    plain one (what the analysis/programs.py registration lints)."""
    import optax

    from horovod_tpu.parallel.train_step import make_split_train_step

    params, batch = _mlp_data()
    plain = make_split_train_step(_mlp_loss, optax.adam(1e-2),
                                  microbatches=2)
    timer = telemetry.StepTimer(flops_per_step=1.0, block=False)
    inst = make_split_train_step(_mlp_loss, optax.adam(1e-2),
                                 microbatches=2, telemetry=timer)
    carry = jax.eval_shape(plain.init, params)
    j1 = jax.make_jaxpr(plain.step)(carry, batch)
    j2 = jax.make_jaxpr(inst.step)(carry, batch)
    assert str(j1) == str(j2)


# ---- bubble accounting -------------------------------------------------


def test_bubble_measured_vs_analytic():
    """Measured bubble math, and agreement with the schedule tables:
    synthetic timings with zero overhead land exactly on the analytic
    interleaved bubble."""
    from horovod_tpu.parallel.pipeline import build_interleaved_schedule

    S, V, M = 4, 2, 8
    sched = build_interleaved_schedule(S, V, M)
    t_sub = 0.010
    # A zero-overhead step takes n_slots subticks of wall time.
    step_time = sched.n_slots * t_sub
    rep = telemetry.bubble_report("interleaved_1f1b", S, M, V,
                                  step_time, t_sub)
    assert rep["measured_bubble"] == pytest.approx(
        sched.bubble_fraction, abs=1e-4)
    assert rep["excess"] == pytest.approx(0.0, abs=1e-4)
    # Overhead shows up as positive excess.
    rep2 = telemetry.bubble_report("interleaved_1f1b", S, M, V,
                                   step_time * 1.25, t_sub)
    assert rep2["excess"] > 0.15
    # Analytic forms of the three schedules.
    assert telemetry.analytic_bubble("gpipe", S, M) == pytest.approx(
        2 * (S - 1) / (2 * M + 2 * (S - 1)))
    assert telemetry.analytic_bubble("1f1b", S, M) == pytest.approx(
        2 * (S - 1) / (M + 2 * (S - 1)))


# ---- exporters ---------------------------------------------------------


def test_scraper_exporters(tmp_path, hvd_core):
    from horovod_tpu.common import eager_ops as ops

    h = ops.allreduce_async(np.ones(32, np.float32), "scrape.0")
    h.synchronize()
    jsonl = tmp_path / "flight.jsonl"
    prom = tmp_path / "metrics.prom"
    scraper = telemetry.MetricsScraper(interval_s=3600,
                                       jsonl_path=str(jsonl),
                                       prom_path=str(prom))
    scraper.scrape_once()
    scraper.scrape_once()
    rows = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert len(rows) == 2
    assert rows[-1]["ops"]["allreduce"]["tensors"] >= 1
    assert rows[-1]["ts"] >= rows[0]["ts"]
    text = prom.read_text()
    assert 'hvdtpu_op_bytes_total{op="allreduce",plane="host",rank="0"}' \
        in text
    assert "hvdtpu_cache_hit_rate" in text


def test_prom_flattening_covers_fully_populated_snapshot():
    """Audit of the Prometheus flattening against a snapshot with EVERY
    section populated: the r13/r14 additions (elastic heal/retry/CRC/
    rejoin counters, per-plane wire.cross_* bytes) must all surface as
    samples — a section silently dropped by the flattener is an
    alerting blind spot, which is how the elastic counters shipped two
    rounds without an exporter row."""
    from horovod_tpu.telemetry.exporters import _flatten_prom

    hist = {"count": 3, "sum_us": 30, "min_us": 5, "max_us": 20,
            "p50_us": 10, "p90_us": 20, "p99_us": 20}
    snap = {
        "initialized": True, "rank": 2, "size": 4,
        "ops": {"allreduce": {"responses": 5, "tensors": 7,
                              "bytes": 4096}},
        "device_ops": {"allgather": {"responses": 1, "tensors": 1,
                                     "bytes": 64}},
        "negotiation_us": hist, "queue_us": hist, "wire_us": hist,
        "fusion": {"fused_responses": 2, "fill_bytes": 100,
                   "capacity_bytes": 400, "fill_ratio": 0.25},
        "cycle": {"count": 9, "stalls": 1, "overrun_us": 12},
        "cache": {"hits": 3, "misses": 1, "entries": 2, "hit_bytes": 99,
                  "hit_rate": 0.75},
        "straggler": {"last_rank_counts": [0, 2, 0, 1],
                      "skew_us": hist},
        "wire": {"tx_bytes": 1000, "rx_bytes": 1000,
                 "tx_logical_bytes": 2000, "rx_logical_bytes": 2000,
                 "compression_ratio": 0.5,
                 "cross_tx_bytes": 250, "cross_rx_bytes": 250,
                 "cross_tx_logical_bytes": 500,
                 "cross_rx_logical_bytes": 500,
                 "cross_compression_ratio": 0.5,
                 "syscalls": {"tx_calls": 40, "rx_calls": 50,
                              "cross_tx_calls": 10,
                              "cross_rx_calls": 12,
                              "per_gb": 45000.0,
                              "channels": [
                                  {"channel": 0, "tx_calls": 30,
                                   "rx_calls": 38},
                                  {"channel": 1, "tx_calls": 10,
                                   "rx_calls": 12}]},
                 "overlap": {"steps": 7, "unattributed_us": 11,
                             "exposed_wire_ms": 5.0,
                             "hidden_wire_ms": 15.0,
                             "overlap_efficiency": 0.75,
                             "intra": {"exposed_us": 5000,
                                       "hidden_us": 15000,
                                       "total_us": 20000,
                                       "overlap_efficiency": 0.75,
                                       "last_exposed_us": 1,
                                       "last_hidden_us": 2,
                                       "last_total_us": 3},
                             "cross": {"exposed_us": 0, "hidden_us": 0,
                                       "total_us": 0,
                                       "overlap_efficiency": 0.0,
                                       "last_exposed_us": 0,
                                       "last_hidden_us": 0,
                                       "last_total_us": 0}}},
        "elastic": {"epoch": 3, "faults_detected": 2,
                    "faults_recovered": 1, "ranks_blacklisted": 1,
                    "ranks_rejoined": 1, "heals": 4, "retries": 6,
                    "crc_errors": 2, "detect_us": hist},
        "errors": 1,
        "knobs": {"fusion_threshold_bytes": 1024},
    }
    text = _flatten_prom(snap, snap["rank"])
    expected = [
        'hvdtpu_wire_cross_tx_bytes_total{rank="2"} 250',
        'hvdtpu_wire_cross_rx_bytes_total{rank="2"} 250',
        'hvdtpu_wire_cross_tx_logical_bytes_total{rank="2"} 500',
        'hvdtpu_wire_cross_rx_logical_bytes_total{rank="2"} 500',
        'hvdtpu_wire_cross_compression_ratio{rank="2"} 0.5',
        'hvdtpu_elastic_heals_total{rank="2"} 4',
        'hvdtpu_elastic_retries_total{rank="2"} 6',
        'hvdtpu_elastic_crc_errors_total{rank="2"} 2',
        'hvdtpu_elastic_ranks_rejoined_total{rank="2"} 1',
        'hvdtpu_elastic_faults_detected_total{rank="2"} 2',
        'hvdtpu_elastic_faults_recovered_total{rank="2"} 1',
        'hvdtpu_elastic_ranks_blacklisted_total{rank="2"} 1',
        'hvdtpu_elastic_epoch{rank="2"} 3',
        'hvdtpu_elastic_detect_p99_us{rank="2"} 20',
        'hvdtpu_wire_tx_bytes_total{rank="2"} 1000',
        'hvdtpu_straggler_last_total{rank="2",straggler="1"} 2',
        'hvdtpu_errors_total{rank="2"} 1',
        # r17 step-anatomy overlap ledger (docs/metrics.md).
        'hvdtpu_overlap_steps_total{rank="2"} 7',
        'hvdtpu_overlap_unattributed_us_total{rank="2"} 11',
        'hvdtpu_overlap_efficiency{rank="2"} 0.75',
        'hvdtpu_overlap_exposed_us_total{plane="intra",rank="2"} 5000',
        'hvdtpu_overlap_hidden_us_total{plane="intra",rank="2"} 15000',
        'hvdtpu_overlap_total_us_total{plane="intra",rank="2"} 20000',
        'hvdtpu_overlap_plane_efficiency{plane="intra",rank="2"} 0.75',
        'hvdtpu_overlap_plane_efficiency{plane="cross",rank="2"} 0.0',
        # r23 syscall accounting (docs/wire.md "Syscall budget"): the
        # io_uring baseline — calls per plane/channel + calls-per-GB.
        'hvdtpu_wire_syscalls_total{direction="tx",rank="2"} 40',
        'hvdtpu_wire_syscalls_total{direction="rx",rank="2"} 50',
        'hvdtpu_wire_cross_syscalls_total{direction="tx",rank="2"} 10',
        'hvdtpu_wire_cross_syscalls_total{direction="rx",rank="2"} 12',
        'hvdtpu_wire_syscalls_per_gb{rank="2"} 45000.0',
        'hvdtpu_wire_channel_syscalls_total{direction="tx",'
        'channel="1",rank="2"} 10',
        'hvdtpu_wire_channel_syscalls_total{direction="rx",'
        'channel="0",rank="2"} 38',
    ]
    for line in expected:
        assert line in text, f"missing exporter row: {line}"
    # Every line is well-formed text-format: "name{labels} value".
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and not name.endswith("{"), line
        float(value)


def test_step_timer_per_plane_wire_split(monkeypatch):
    """plane_wire_summary splits the transport deltas intra vs cross
    and reconciles per-plane compression independently (cross-hop-only
    bf16: cross ratio 0.5, intra 1.0, intra+cross == total)."""
    from horovod_tpu.telemetry import core as tcore

    snaps = []
    # Per step: total tx grows 1200 (logical 1600); the cross slice of
    # it grows 200 (logical 400) -> intra 1000/1200, cross 200/400.
    for i in range(6):
        snaps.append({
            "initialized": True, "rank": 0, "size": 2, "ops": {},
            "device_ops": {},
            "cache": {"hit_rate": 0.0}, "cycle": {"stalls": 0},
            "wire": {"tx_bytes": 1200 * i, "rx_bytes": 1200 * i,
                     "tx_logical_bytes": 1600 * i,
                     "rx_logical_bytes": 1600 * i,
                     "cross_tx_bytes": 200 * i,
                     "cross_rx_bytes": 200 * i,
                     "cross_tx_logical_bytes": 400 * i,
                     "cross_rx_logical_bytes": 400 * i},
        })
    it = iter(snaps + snaps[-1:] * 4)
    monkeypatch.setattr(tcore, "snapshot", lambda: next(it))
    timer = telemetry.StepTimer(block=False)
    for _ in range(3):
        timer.start_step()
        timer.end_step()
    planes = timer.plane_wire_summary(skip_first=False)
    assert planes["intra"]["tx_bytes_per_step"] == 1000
    assert planes["intra"]["compression_ratio"] == pytest.approx(1000 / 1200)
    assert planes["cross"]["tx_bytes_per_step"] == 200
    assert planes["cross"]["compression_ratio"] == pytest.approx(0.5)
    # intra + cross reconcile exactly with the total wire counters.
    total = timer.wire_bytes_per_step
    for (tx, _txl), p in zip(total, timer.plane_bytes_per_step):
        assert p[0] + p[2] == tx
    assert "plane_wire" in timer.summary()


def test_step_timer_overlap_summary(monkeypatch):
    """overlap_summary aggregates the core ledger's per-step last_*
    rows: per-plane exposed/hidden/total reconcile exactly and the
    combined efficiency is hidden/total across planes."""
    from horovod_tpu.telemetry import core as tcore

    snap = {
        "initialized": True, "rank": 0, "size": 2, "ops": {},
        "device_ops": {}, "cache": {"hit_rate": 0.0},
        "cycle": {"stalls": 0},
        "wire": {"tx_bytes": 0, "tx_logical_bytes": 0,
                 "cross_tx_bytes": 0, "cross_tx_logical_bytes": 0,
                 "overlap": {
                     "steps": 1,
                     "intra": {"last_exposed_us": 4000,
                               "last_hidden_us": 6000,
                               "last_total_us": 10000},
                     "cross": {"last_exposed_us": 1000,
                               "last_hidden_us": 1000,
                               "last_total_us": 2000},
                 }},
    }
    monkeypatch.setattr(tcore, "snapshot", lambda: snap)

    def fake_mark(begin=True, owner=None):
        # Mirror the real step_mark's owner bookkeeping: end_step
        # asserts the window is still the timer's before closing it.
        tcore._window_owner = owner if begin else None
        return 1

    monkeypatch.setattr(tcore, "step_mark", fake_mark)
    timer = telemetry.StepTimer(block=False)
    for _ in range(2):
        timer.start_step()
        timer.end_step()
    ov = timer.overlap_summary(skip_first=False)
    # mean_ prefix on purpose: the snapshot/healthz expose CUMULATIVE
    # exposed_wire_ms — per-step means must not share the key.
    assert ov["intra"]["mean_exposed_wire_ms"] == 4.0
    assert ov["intra"]["mean_hidden_wire_ms"] == 6.0
    assert ov["intra"]["mean_total_wire_ms"] == 10.0
    assert ov["intra"]["overlap_efficiency"] == pytest.approx(0.6)
    assert ov["cross"]["overlap_efficiency"] == pytest.approx(0.5)
    # Combined: hidden 7ms of total 12ms.
    assert ov["overlap_efficiency"] == pytest.approx(7 / 12)
    assert timer.summary()["overlap"] is not None


class _FakeBasics:
    """Just enough of HorovodBasics' step-window surface to replay the
    id-reuse collision python-side: ids restart after metrics_reset,
    exactly like the core registry."""

    def __init__(self):
        self.next_id = 0
        self.open = -1

    def step_mark(self, begin=True):
        if begin:
            self.open = self.next_id
            self.next_id += 1
            return self.open
        sid, self.open = self.open, -1
        return sid

    def step_id(self):
        return self.open

    def metrics_reset(self):
        self.next_id = 0
        self.open = -1


def test_step_window_single_owner_after_id_reuse(monkeypatch):
    """Regression: an explicit StepTimer scope and the fused
    optimizer's implicit boundary in the same iteration must keep ONE
    owner per window. Core step ids restart after metrics_reset(), so
    the optimizer's remembered boundary id can collide with a
    StepTimer-opened window — the id-only deference check then stole
    the window mid-step, splitting the step's overlap ledger across
    two half-windows."""
    from horovod_tpu.jax import optimizer as hvd_opt
    from horovod_tpu.telemetry import core as tcore

    monkeypatch.setattr(tcore, "_basics", _FakeBasics())
    monkeypatch.setattr(tcore, "_window_owner", None)
    monkeypatch.setattr(hvd_opt, "_last_boundary_id", None)

    # Implicit lane first: the optimizer marks a boundary (window 0)
    # and remembers its id.
    hvd_opt._mark_optimizer_step()
    assert tcore.step_id() == 0
    assert tcore.window_owner() == "optimizer"
    assert hvd_opt._last_boundary_id == 0

    # A registry reset (bench phase change, test isolation) restarts
    # the core's ids...
    tcore.metrics_reset()
    assert tcore.window_owner() is None

    # ...so the next explicit scope REUSES id 0.
    timer = telemetry.StepTimer(block=False)
    timer.start_step()
    assert tcore.step_id() == 0  # collides with the remembered id

    # The optimizer's implicit boundary inside the timed iteration must
    # defer to the explicit scope despite the id collision.
    hvd_opt._mark_optimizer_step()
    assert tcore.step_id() == 0
    assert tcore.window_owner() == "StepTimer"

    # The timer closes its own window cleanly.
    timer.end_step()
    assert tcore.step_id() == -1
    assert tcore.window_owner() is None

    # Implicit lane still drives the marks when no explicit scope is
    # active.
    hvd_opt._mark_optimizer_step()
    assert tcore.window_owner() == "optimizer"


def test_step_timer_refuses_stolen_window(monkeypatch):
    """A window re-opened by another driver mid-step fails loudly at
    end_step instead of booking a fragmented half-window."""
    from horovod_tpu.telemetry import core as tcore

    monkeypatch.setattr(tcore, "_basics", _FakeBasics())
    monkeypatch.setattr(tcore, "_window_owner", None)

    timer = telemetry.StepTimer(block=False)
    timer.start_step()
    # Rogue second driver closes and re-opens the window mid-step.
    tcore.step_mark(False)
    tcore.step_mark(True, owner="optimizer")
    with pytest.raises(RuntimeError, match="owned by 'optimizer'"):
        timer.end_step()
    # The timer reset its scope: the next start/end pair is usable.
    tcore.step_mark(False)
    timer.start_step()
    timer.end_step()


# ---- cross-rank trace merge -------------------------------------------


def _synthetic_timeline(rank, clock_offset_us, straggle_us=0,
                        tensors=("g0", "g1"), steps=3):
    """A rank's Chrome-trace timeline with its own clock origin.

    True (wall) submit time of tensor t at step s is
    ``1000*s + 10*idx (+ straggle_us)``; each rank's recorded ts are
    shifted by its clock offset, which CLOCK_SYNC exposes."""
    events = [
        {"name": "process_name", "ph": "M", "pid": rank,
         "args": {"name": f"rank {rank}"}},
        {"name": "CLOCK_SYNC", "ph": "i", "ts": 0, "pid": rank,
         "tid": 0, "s": "p",
         "args": {"unix_us": 1_700_000_000_000_000 + clock_offset_us,
                  "rank": rank}},
    ]
    for s in range(steps):
        for i, t in enumerate(tensors):
            true_b = 1000 * s + 10 * i + straggle_us
            # The coordinator's response broadcast lands on every rank
            # at (near) the same wall instant — after the straggler —
            # which is exactly what the fallback alignment leans on.
            true_e = 1000 * s + 10 * i + 800
            for ph, ts in (("B", true_b), ("E", true_e)):
                events.append({"name": "NEGOTIATE", "ph": ph,
                               "ts": ts - clock_offset_us, "pid": rank,
                               "tid": i, "args": {"tensor": t}})
    return events


def _write_traces(tmp_path, with_sync=True):
    """4 ranks, distinct clock origins, rank 2 always 300 us late."""
    paths = []
    for rank in range(4):
        ev = _synthetic_timeline(
            rank, clock_offset_us=rank * 50_000,
            straggle_us=300 if rank == 2 else 0)
        if not with_sync:
            ev = [e for e in ev if e["name"] != "CLOCK_SYNC"]
        p = tmp_path / f"tl.{rank}.json"
        p.write_text(json.dumps(ev))
        paths.append(str(p))
    return paths


def test_straggler_merge_4_ranks(tmp_path):
    """ISSUE-4 acceptance: one Perfetto-loadable merged trace + a
    per-rank straggler table that blames the planted straggler."""
    paths = _write_traces(tmp_path)
    merged, skew = report.merge(paths)

    # Single valid Chrome-trace array: list of dicts, every event has
    # the fields Perfetto needs, ts sorted.
    assert isinstance(merged, list) and merged
    ts = [e["ts"] for e in merged if "ts" in e]
    assert ts == sorted(ts)
    assert {e["pid"] for e in merged} == {0, 1, 2, 3}
    json.loads(json.dumps(merged))

    # Straggler table: rank 2 arrived last on every matched collective,
    # with ~300us skew; others near zero.
    assert set(skew["per_rank"]) == {0, 1, 2, 3}
    assert skew["matched_events"] == 6  # 2 tensors x 3 steps
    assert skew["per_rank"][2]["last_count"] == 6
    assert skew["per_rank"][2]["mean_skew_us"] == pytest.approx(300, abs=5)
    for r in (0, 1, 3):
        assert skew["per_rank"][r]["last_count"] == 0
        assert skew["per_rank"][r]["mean_skew_us"] < 5
    assert skew["worst_tensors"][0]["last_rank"] == 2


def test_straggler_merge_negotiate_fallback(tmp_path):
    """Without CLOCK_SYNC (older traces), the NEGOTIATE-end median
    alignment recovers the offsets and still blames rank 2."""
    paths = _write_traces(tmp_path, with_sync=False)
    merged, skew = report.merge(paths)
    assert skew["per_rank"][2]["last_count"] == 6
    assert skew["per_rank"][2]["mean_skew_us"] == pytest.approx(300, abs=5)


def test_report_cli(tmp_path, capsys):
    paths = _write_traces(tmp_path)
    out = tmp_path / "merged.json"
    skew_out = tmp_path / "skew.json"
    rc = report.main([*paths, "-o", str(out),
                      "--skew-json", str(skew_out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert len(merged) > 0
    skew = json.loads(skew_out.read_text())
    assert skew["per_rank"]["2"]["last_count"] == 6
    captured = capsys.readouterr().out
    assert "rank" in captured and "merged.json" in captured


def test_report_fault_events_in_straggler_table(tmp_path, capsys):
    """Per-rank metrics snapshots fold elastic fault events (epoch,
    fault counts, detection latency) into the straggler table — the
    report names churny hosts, not just slow ones (docs/elastic.md)."""
    paths = _write_traces(tmp_path)
    snap_paths = []
    for rank in range(4):
        snap = {"rank": rank,
                "elastic": {"epoch": 1, "faults_detected": 1,
                            "faults_recovered": 1,
                            "ranks_blacklisted": 1,
                            "detect_us": {"count": 1, "p50_us": 2048}}}
        if rank == 2:  # the flaky rank keeps re-detecting faults
            snap["elastic"]["faults_detected"] = 3
        p = tmp_path / f"snap.{rank}.json"
        p.write_text(json.dumps(snap))
        snap_paths.append(str(p))

    _, skew = report.merge(paths)
    report.attach_fault_events(skew, snap_paths)
    assert skew["fault_events"][2]["faults_detected"] == 3
    assert skew["per_rank"][2]["faults_detected"] == 3
    assert skew["per_rank"][2]["epoch"] == 1
    text = report.format_skew_table(skew)
    assert "faults" in text and "epoch" in text and "2048" in text

    # CLI wiring: --snapshots lands fault_events in the skew JSON.
    out = tmp_path / "merged.json"
    skew_out = tmp_path / "skew.json"
    rc = report.main([*paths, "-o", str(out), "--skew-json",
                      str(skew_out), "--snapshots", *snap_paths])
    assert rc == 0
    skew_json = json.loads(skew_out.read_text())
    assert skew_json["fault_events"]["2"]["faults_detected"] == 3
    assert "faults" in capsys.readouterr().out


def test_real_timeline_has_clock_sync(tmp_path, hvd_core):
    """The core's runtime timeline carries the CLOCK_SYNC anchor and
    stays valid JSON (the merge's preferred alignment path)."""
    from horovod_tpu.common import eager_ops as ops

    path = tmp_path / "tl.json"
    hvd_core.start_timeline(str(path))
    h = ops.allreduce_async(np.ones(8, np.float32), "tl.x")
    h.synchronize()
    hvd_core.stop_timeline()
    events = json.loads(path.read_text())
    sync = [e for e in events if e and e.get("name") == "CLOCK_SYNC"]
    assert len(sync) == 1
    assert sync[0]["args"]["unix_us"] > 1_000_000_000_000_000
    rank, loaded = report.load_timeline(str(path))
    assert rank == 0
    assert any(e.get("name") == "NEGOTIATE" for e in loaded)
