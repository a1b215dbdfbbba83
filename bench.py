"""Benchmark entry point (driver contract): JSON lines to stdout.

Measures llama train steps on the available accelerator and reports
model-FLOPs utilization. MFU is the single-chip analog of the
reference's headline metric (scaling efficiency ≈ how close to hardware
roofline the framework runs — docs/benchmarks.rst cites ~90% of linear
at 128 GPUs); ``vs_baseline`` is measured MFU / 0.40, i.e. 1.0 marks the
40% MFU bar a well-tuned transformer stack hits on TPU at this scale.

A plain run emits FOUR rows (the driver tail-parses the LAST line, so
the pure-bf16 flagship stays last):

1. ``llama_train_step_mfu_mixed`` — 809M, fp32 master weights + fp32
   adam moments (``parallel.master_weights``): the numerically safe
   recipe.
2. ``llama_train_step_mfu_809m`` — the SAME 809M size in pure bf16:
   the safety cost at fixed size is one subtraction against row 1.
3. ``llama_train_step_mfu_eager`` — the flagship trained through the
   EAGER Horovod path: jitted fwd/bwd, then ``hvd.grouped_allreduce``
   of every gradient over the xla_ici device plane (size=1 exercises
   enqueue → negotiate → cached-program replay each step, the
   reference's `DistributedOptimizer` shape — docs/benchmarks.rst
   measures hvd-wrapped training, not a raw-framework program), then a
   jitted optimizer apply.
4. ``llama_train_step_mfu`` — the 1.43B pure-bf16 flagship, split
   grad/apply SPMD step, measured and emitted last so the driver's
   tail-parse gets the headline. BOTH optimizer-apply formulations are
   measured (optax split apply vs the single-pass
   ``parallel.fused_adam``), recorded in a ``llama_update_sweep`` row
   emitted just before the headline, and the winner headlines.

Every train-step row needs a TPU: a run that finds none FAILS (no CPU
timing is ever printed under a device metric's name), a failed row is a
failed run — nothing is retried under the same metric name — and an
unknown ``device_kind`` is an error, not a default peak. One process
holds the chip for the whole default run; only ``--sweep`` spawns chip
children (one per point, for crash isolation), and its parent stays off
jax and asks the platform in a child.

``--mixed`` emits only row 1 (back-compat); ``--quick`` only the
flagship rows; ``--sweep`` runs the on-chip tuning lane (remat
save-set, flash block shapes, microbatch accumulation — see
_run_sweep).
"""

import functools
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import (
    LlamaConfig,
    llama_init,
    llama_loss,
)
from horovod_tpu.utils.compile_cache import enable_compile_cache
from horovod_tpu.utils.devices import (  # noqa: F401 (re-exported)
    PEAK_BF16_FLOPS,
    match_device_table,
    require_tpu,
)

# Row-format version stamped on every emitted row (emit() below): bump
# when a field is renamed or its meaning moves, so `--diff` and
# `python -m horovod_tpu.telemetry.perfwatch` can refuse mismatched row
# formats loudly instead of mis-comparing (schema 1 = the r17 format).
BENCH_SCHEMA = 1


def _peak_flops(device):
    return match_device_table(device, PEAK_BF16_FLOPS)


# 1.4B decoder: profiled sweet spot for one 16G-HBM chip. Pure-bf16
# parameter storage (param_dtype) halves param/grad/optimizer HBM and is
# what lets >1B params fit at all; larger d_model raises matmul
# efficiency (0.50 MFU at d2048 vs 0.47 at d1536/667M fp32 params vs
# 0.45 at d1024/319M); remat="attn" beats full remat (the flash kernel
# makes saving one attention output per layer enough); d2560 regresses
# (0.45). head_dim 128 (16 heads, not 32) feeds the MXU full-depth
# contractions in the flash kernel: 0.525 -> 0.63 MFU at identical
# param count (r4 sweep, docs/benchmarks.md). Round-5 geometry sweep at
# fixed ~1.4B params: fewer-but-wider layers amortize the per-layer
# fixed costs (norm/rope/residual chains, flash launches, scan
# overhead) — L14/d_ff 13312 beats L20/8192 by ~2 MFU points — and 4:1
# GQA (n_kv 4, the llama-3/mistral ratio) trims the kv projections and
# flash dkv work for another ~1.5 (docs/benchmarks.md r5 table).
# Donated buffers throughout.
def _flagship_cfg():
    return LlamaConfig(vocab_size=32768, d_model=2048, n_layers=14,
                       n_heads=16, n_kv_heads=4, d_ff=13312,
                       dtype="bfloat16", remat="attn+gate",
                       param_dtype="bfloat16")


# TPU compiler options for the fused train-step jits: the stock 16 MB
# scoped-VMEM budget under-buffers the big fused matmuls at bench
# shapes (+~1 MFU point at 64 MB, measured r5; 96 MB regresses).
def _step_jit_kwargs():
    if jax.devices()[0].platform != "tpu":
        return {}  # a TPU compiler option; CPU tests of the step omit it
    return {"compiler_options": {"xla_tpu_scoped_vmem_limit_kib":
                                 "65536"}}


# 809M: the largest size whose fp32 master + fp32 adam moments (12B HBM
# per param, parallel.master_weights) fit one 16G chip — and therefore
# the size where mixed-vs-pure compares apples to apples. Same
# head_dim-128 recipe as the flagship (12 heads at d1536).
def _same_size_cfg(param_dtype):
    return LlamaConfig(vocab_size=32768, d_model=1536, n_layers=20,
                       n_heads=12, n_kv_heads=6, d_ff=6144,
                       dtype="bfloat16", remat="attn+gate",
                       param_dtype=param_dtype)


def _mfu_row(metric, label_extra, n_params, cfg, batch, seq, dt):
    tokens_per_step = batch * seq
    # Standard (PaLM appendix B) model-FLOPs: 6N per token plus the
    # 12*L*T*d attention term; remat recompute is NOT credited.
    flops_per_token = (6 * n_params
                       + 12 * cfg.n_layers * seq * cfg.d_model)
    mfu = (flops_per_token * tokens_per_step / dt
           / _peak_flops(jax.devices()[0]))
    return {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": f"MFU ({n_params/1e6:.0f}M params, {label_extra}, "
                f"{tokens_per_step} tok/step, "
                f"{tokens_per_step/dt:.0f} tok/s, "
                f"{dt*1e3:.0f} ms/step, "
                f"{jax.devices()[0].device_kind})",
        "vs_baseline": round(mfu / 0.40, 3),
    }


def _data(cfg, batch, seq):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _timed(step, carry, data, steps, what):
    t0 = time.perf_counter()
    loss, carry = step(carry, data)
    # Block on the whole output tree: some PJRT transports surface the
    # scalar loss before the step's trailing ops finish.
    jax.block_until_ready((loss, carry))
    print(f"{what}: compile+first step "
          f"{time.perf_counter() - t0:.1f}s loss={float(loss):.3f}",
          file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(steps):  # the runtime bounds its own run-ahead
        loss, carry = step(carry, data)
    jax.block_until_ready((loss, carry))
    dt = (time.perf_counter() - t0) / steps
    del carry
    return dt


def run_spmd(cfg, batch, seq, steps, metric, label, update="split",
             microbatches=1):
    """Split-program train step (``parallel.make_split_train_step``):
    one jitted grad program — called once per microbatch, accumulating
    into donated gradient buffers — and one jitted optimizer-apply
    program. Splitting the adam update out of the grad program measures
    ~3% FASTER than the single fused-into-grad jit at flagship shape
    (573 -> 552 ms, r5) — the monolith's interleaved update schedules
    worse — and it is the same program structure the eager-Horovod row
    uses minus the collective.

    ``update``: "split" = optax adam (updates tree + apply_updates, the
    r5 baseline), "fused" = ``parallel.fused_adam`` (the whole update
    as ONE elementwise pass per leaf — the r6 fewer-passes-over-params
    attack on the adam HBM tail). ``--quick`` measures both and
    headlines the winner (the ``llama_update_sweep`` row records the
    comparison)."""
    from horovod_tpu.parallel import fused_adam, make_split_train_step

    tx = fused_adam(3e-4) if update == "fused" else optax.adam(3e-4)

    # n_params from shapes only — no device allocation.
    shapes = jax.eval_shape(lambda k: llama_init(cfg, k),
                           jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(shapes))

    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), tx,
        microbatches=microbatches, jit_kwargs=_step_jit_kwargs())

    dt = _timed(ts.step, ts.init(llama_init(cfg, jax.random.PRNGKey(0))),
                _data(cfg, batch, seq), steps, metric)
    return _mfu_row(metric, label, n_params, cfg, batch, seq, dt)


def run_spmd_fused(cfg, batch, seq, steps, metric, label):
    """Single fused jit step (loss + grads + adam in one program) —
    the long-context bench's step (benchmarks/long_context_bench.py: at
    those activation footprints the split layout's separate gradient
    tree is what does not fit)."""
    tx = optax.adam(3e-4)
    shapes = jax.eval_shape(lambda k: llama_init(cfg, k),
                           jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(shapes))

    @functools.partial(jax.jit, donate_argnums=(0,),
                       **_step_jit_kwargs())
    def step(carry, data):
        params, opt = carry
        loss, grads = jax.value_and_grad(llama_loss)(params, data, cfg)
        updates, opt = tx.update(grads, opt, params)
        return loss, (optax.apply_updates(params, updates), opt)

    def make_carry():
        params = llama_init(cfg, jax.random.PRNGKey(0))
        return (params, tx.init(params))

    dt = _timed(step, make_carry(), _data(cfg, batch, seq), steps,
                metric)
    return _mfu_row(metric, label, n_params, cfg, batch, seq, dt)


def run_mixed(cfg, batch, seq, steps):
    """fp32 master weights + fp32 adam moments, bf16 compute
    (parallel.master_weights) — the numerically safe recipe.
    param_dtype fp32: the master aliases the init tree (no bf16 rounding
    of initial weights, no extra init transient)."""
    from horovod_tpu.parallel import master_weights

    params = llama_init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    mw = master_weights(optax.adam(3e-4))
    carry = mw.init(params)
    del params

    @functools.partial(jax.jit, donate_argnums=(0,),
                       **_step_jit_kwargs())
    def step(carry, data):
        p = mw.compute_params(carry)
        loss, grads = jax.value_and_grad(llama_loss)(p, data, cfg)
        return loss, mw.apply(carry, grads)

    dt = _timed(step, carry, _data(cfg, batch, seq), steps,
                "llama_train_step_mfu_mixed")
    return _mfu_row("llama_train_step_mfu_mixed",
                    "fp32-master mixed precision", n_params, cfg, batch,
                    seq, dt)


def _eager_parts(cfg):
    """Shared scaffolding for the eager step builders: committed
    params/opt, the jitted grad program, and the params/opt-donating
    adam apply program. ONE copy so the grouped and ungrouped lanes can
    only ever differ by their allreduce granularity."""
    # COMMITTED to the device from the start: the data plane's staging
    # device_put commits the gradients, so apply_fn outputs would flip
    # params from uncommitted to committed after step one — a new jit
    # signature, i.e. a silent 12 s mid-loop recompile of grad_fn that
    # once cost this row half its MFU.
    # This process's device: under a multi-rank launch jax.devices()[0]
    # is rank 0's chip.
    dev = jax.local_devices()[0]
    params = jax.device_put(llama_init(cfg, jax.random.PRNGKey(0)), dev)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tx = optax.adam(3e-4)
    opt = jax.device_put(tx.init(params), dev)

    grad_fn = jax.jit(
        lambda p, d: jax.value_and_grad(llama_loss)(p, d, cfg),
        **_step_jit_kwargs())

    # Grads are NOT donated here: they arrive as donation-ALIASED
    # outputs of the device-plane identity program, and XLA refuses to
    # re-donate an aliased buffer (the "donated buffers were not
    # usable" warning) — listing them would only add noise. params/opt
    # donation is what matters for the peak.
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply_fn(grads, params, opt):
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt

    return (params, opt), n_params, grad_fn, apply_fn


def make_eager_step(cfg):
    """Eager-Horovod step builder, shared with
    benchmarks/autotune_bench.py (hvd must already be initialized):
    jitted grad program, ``hvd.grouped_allreduce`` of the gradient tree
    over the device plane, jitted adam apply. Returns
    ``(step, (params, opt), n_params)`` with
    ``step(carry, data) -> (loss, carry)``."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax.optimizer import allreduce_gradients

    carry0, n_params, grad_fn, apply_fn = _eager_parts(cfg)

    def step(carry, data):
        params, opt = carry
        loss, grads = grad_fn(params, data)
        # Donated: the fused device program reuses the gradients' HBM.
        grads = allreduce_gradients(grads, op=hvd.Average, donate=True)
        params, opt = apply_fn(grads, params, opt)
        return loss, (params, opt)

    return step, carry0, n_params


def make_eager_ungrouped_step(cfg):
    """UNGROUPED per-parameter eager step: every gradient is enqueued
    as its OWN allreduce — layer-stacked leaves are unstacked into
    per-layer tensors first, the granularity a per-parameter framework
    hands Horovod (183 small allreduces/step at the 809M 20-layer
    geometry) — so the core's fusion threshold and cycle time genuinely
    bind: the background loop must re-batch the flood of small tensors
    into fused buffers every cycle. This is the workload
    ``benchmarks/autotune_bench.py --ungrouped`` tunes (VERDICT r5 #4:
    the grouped row was a null because one pre-grouped allreduce leaves
    the knobs nothing to do). Returns ``(step, carry, n_params)`` like
    :func:`make_eager_step`."""
    import horovod_tpu.jax as hvd

    carry0, n_params, grad_fn, apply_fn = _eager_parts(cfg)

    def step(carry, data):
        params, opt = carry
        loss, grads = grad_fn(params, data)
        flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
        handles, rebuild = [], []
        for i, (path, leaf) in enumerate(flat):
            stacked = "layers" in jax.tree_util.keystr(path)
            if stacked:
                # one allreduce PER LAYER, as a per-parameter frontend
                # would issue them (stable names keep the response
                # cache hot across steps)
                hs = [hvd.allreduce_async(leaf[j], name=f"ug{i}.{j}",
                                          op=hvd.Average)
                      for j in range(leaf.shape[0])]
                handles.extend(hs)
                rebuild.append((True, len(hs)))
            else:
                handles.append(hvd.allreduce_async(
                    leaf, name=f"ug{i}", op=hvd.Average))
                rebuild.append((False, 1))
        outs = [h.synchronize() for h in handles]
        leaves, k = [], 0
        for stacked, n in rebuild:
            if stacked:
                leaves.append(jnp.stack(outs[k:k + n]))
            else:
                leaves.append(outs[k])
            k += n
        grads = jax.tree.unflatten(treedef, leaves)
        params, opt = apply_fn(grads, params, opt)
        return loss, (params, opt)

    return step, carry0, n_params


def run_eager(cfg, batch, seq, steps, label):
    """The eager Horovod path: every step enqueues the full gradient
    tree on the core (one atomic group), the background thread
    negotiates it (response-cache bitvector in steady state) and
    replays the cached fused XLA allreduce program on the chip, then a
    jitted adam applies the averaged gradients. Reference analog:
    §3.2's hot loop (torch DistributedOptimizer + NCCL backend)."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import xla_ici

    require_tpu("the eager-Horovod bench row")
    hvd.init()  # on a TPU, init brings the device plane up or raises
    if not xla_ici.active():
        raise RuntimeError(
            "the eager row measures the xla_ici device plane, which is "
            "off (HOROVOD_XLA_DATA_PLANE=0 or HOROVOD_CROSS_PLANE=ring)")

    step, carry, n_params = make_eager_step(cfg)
    data = _data(cfg, batch, seq)
    try:
        from horovod_tpu import telemetry
        from horovod_tpu.telemetry import predict

        # Static predictor: the SAME grad-tree byte volume the
        # telemetry tests reconcile against (dtype-exact — eval_shape
        # of the true grad tree, not n_params x an assumed width).
        predicted = predict.grad_tree_bytes(
            lambda p, d: llama_loss(p, d, cfg), carry[0], data)
        # Wire-goodput rides along for free: the loop runs steps+1
        # steps (compile step included) and the core's byte counters
        # are read before/after (telemetry row below).
        snap0 = telemetry.total_collective_bytes()
        dt = _timed(step, carry, data, steps,
                    "llama_train_step_mfu_eager")
        moved = telemetry.total_collective_bytes() - snap0
        snap = telemetry.snapshot()
    finally:
        hvd.shutdown()
    per_step = moved / (steps + 1) if steps else moved
    telemetry_row = {
        "metric": "telemetry_eager",
        # Steady-state goodput: per-step payload over the post-compile
        # step time _timed measured (wall including the compile step
        # would underreport by the compile/step ratio).
        "wire_goodput_gbps": round(per_step / dt / 1e9, 4),
        "bytes_per_step": per_step,
        "predicted_bytes_per_step": predicted,
        "byte_reconciliation": round(per_step / predicted, 4)
        if predicted else None,
        "cache_hit_rate": round(snap["cache"]["hit_rate"], 4),
        "cycle_stalls": snap["cycle"]["stalls"],
        "unit": "steady-state collective payload GB/s, eager lane "
                "(hvd.metrics() deltas; predicted = grad-tree bytes "
                "via telemetry.predict)",
    }
    return [telemetry_row,
            _mfu_row("llama_train_step_mfu_eager", label, n_params, cfg,
                     batch, seq, dt)]


def full_run_plan(batch, seq, steps):
    """Ordered (name, thunk) rows of the full accelerator run.

    The row order is part of the output format: the flagship SPMD row
    stays LAST because the driver tail-parses the final line, and the
    eager flagship stays first as in every earlier record.
    `_check_plan_order` (called by main, pinned by
    tests/single/test_bench_plan.py) refuses any reordering. (The order
    once guarded a heap that fragmented across configs; on the current
    chip a config's memory is returned when it is dropped — PR 21 ran
    the 809M config and then the flagship in one process.)
    """
    return [
        ("eager_flagship",
         lambda: run_eager(_flagship_cfg(), batch, seq, steps,
                           "pure-bf16 eager hvd")),
        ("mixed_809m",
         lambda: run_mixed(_same_size_cfg("float32"), batch, seq, steps)),
        ("spmd_809m",
         lambda: run_spmd(_same_size_cfg("bfloat16"), batch, seq, steps,
                          "llama_train_step_mfu_809m",
                          "pure-bf16 same-size")),
        ("spmd_flagship", lambda: _quick_rows(batch, seq, steps)),
    ]


def _quick_rows(batch, seq, steps):
    """The flagship rows (`--quick`, and the last entry of the full
    plan): measure the split-apply baseline, then the single-pass
    fused-adam variant; yield a ``llama_update_sweep`` row recording
    both, then the BETTER one as the headline (last line — the driver
    tail-parses it). Either formulation failing fails the run."""
    base = run_spmd(_flagship_cfg(), batch, seq, steps,
                    "llama_train_step_mfu", "pure-bf16")
    gc.collect()
    fused = run_spmd(_flagship_cfg(), batch, seq, steps,
                     "llama_train_step_mfu", "pure-bf16 fused-adam",
                     update="fused")
    sweep = {
        "metric": "llama_update_sweep",
        "update_split": base["value"],
        "update_fused": fused["value"],
        "unit": "MFU; optax split apply vs single-pass fused adam "
                "(parallel.fused_adam), flagship shape",
    }
    return [sweep, base if base["value"] >= fused["value"] else fused]


# The one bench shape (batch, seq, steps), read by every lane, so the
# headline row can never silently run at a different shape than the
# comparison rows. 15 steps (~8.5 s of stepping per row) tightens the
# run-to-run spread the 10-step windows showed (±1.5%).
_BENCH_SHAPE = (4, 2048, 15)

_EXPECTED_PLAN = ("eager_flagship", "mixed_809m", "spmd_809m",
                  "spmd_flagship")


def _check_plan_order(plan):
    names = tuple(name for name, _ in plan)
    if not names or names[0] != "eager_flagship":
        raise RuntimeError(
            f"bench plan reordered: the eager flagship must run FIRST "
            f"(row order is part of the format, see full_run_plan); "
            f"got {list(names)}")
    if names[-1] != "spmd_flagship":
        raise RuntimeError(
            f"bench plan reordered: the SPMD flagship must run LAST "
            f"(the driver tail-parses the final line); got {list(names)}")
    if names != _EXPECTED_PLAN:
        raise RuntimeError(
            f"bench plan changed: expected {list(_EXPECTED_PLAN)}, got "
            f"{list(names)} — if the change is intentional, update "
            f"_EXPECTED_PLAN")


def _require_tpu_probe(what):
    """Fail unless device 0 is a TPU — asked in a CHILD process, for a
    parent (`--sweep`) that must not initialize its own jax client
    while its chip children are still to run (one process per chip). A
    probe that fails is a failed run: "unknown" is not a platform."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        stdout=subprocess.PIPE, text=True, timeout=300)
    if out.returncode != 0 or not out.stdout.strip():
        raise RuntimeError(f"platform probe child exited "
                           f"{out.returncode}; its stderr is above")
    platform = out.stdout.strip().splitlines()[-1]
    if platform != "tpu":
        raise SystemExit(
            f"bench.py {what} measures the TPU and the probe found "
            f"{platform!r}; the host-only lanes (--ring-busbw, "
            "--events-overhead, --scale, --serving, --zero-sweep, "
            "--fusion, --fleet-util, --diff, --lint) need no chip")


# Child body for one events_overhead rank: the ungrouped eager shape
# (many small per-tensor allreduces per step, stable names riding the
# response-cache bitvector) — the workload where per-response event
# recording would hurt if it could. Pure host, no jax import.
_EVENTS_BENCH_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
from horovod_tpu.common import eager_ops as ops
from horovod_tpu.common.basics import HorovodBasics

cfg = json.loads(os.environ["EVENTS_BENCH_CFG"])
b = HorovodBasics()
b.init()
rank = b.rank()
tensors = [np.full(cfg["elems"], float(rank + 1 + i), np.float32)
           for i in range(cfg["tensors"])]

def step():
    hs = [ops.allreduce_async(t, f"ug{i}")
          for i, t in enumerate(tensors)]
    for h in hs:
        h.synchronize()

for _ in range(2):  # warmup: reach response-cache steady state
    step()
t0 = time.perf_counter()
for _ in range(cfg["steps"]):
    step()
dt = (time.perf_counter() - t0) / cfg["steps"]
if rank == 0:
    print("EVENTS_BENCH_POINT " + json.dumps(
        {"step_s": dt, "events_head": int(b.lib.hvdtpu_events_head())}))
b.shutdown()
"""


def _control_plane_scaling_rows(world_sizes=None):
    """The `control_plane_scaling` rows (docs/scale.md): flat-vs-tree
    negotiation latency curves from the simulated large-world harness
    (csrc/simworld.cc — thread-per-rank, in-process, no accelerator).
    Both curves per world size, so the tree gather's sub-linear claim
    is checkable against the sequential baseline from the same run."""
    from horovod_tpu.simworld import scaling_profile

    try:
        return scaling_profile(world_sizes=world_sizes) \
            if world_sizes else scaling_profile()
    except Exception as e:  # noqa: BLE001 — a starved CI box must not
        # lose the rest of the bench run to the 256-thread point
        return [{"metric": "control_plane_scaling",
                 "error": f"{type(e).__name__}: {e}"}]


def _events_overhead_rows(ranks=2, tensors=183, elems=2048, steps=8,
                          repeats=3):
    """Event-ring overhead on the eager ungrouped lane: `tensors` small
    per-parameter allreduces per step (the 183-allreduce r07 shape),
    measured with the flight recorder on (default) vs off
    (HOROVOD_EVENTS=0), best-of-`repeats` per config to shed loopback
    noise. The acceptance bar is < 2% regression with events on —
    recording is one fetch_add + a handful of relaxed stores on the
    paths that fire per response/chunk (csrc/events.h)."""
    cfg = json.dumps({"tensors": tensors, "elems": elems,
                      "steps": steps})
    best = {}
    heads = {}
    try:
        for _ in range(repeats):
            for name, knob in (("on", "1"), ("off", "0")):
                point = _run_loopback_ranks(
                    _EVENTS_BENCH_CHILD, "EVENTS_BENCH_POINT", ranks,
                    {"HOROVOD_EVENTS": knob, "EVENTS_BENCH_CFG": cfg})
                if name not in best or point["step_s"] < best[name]:
                    best[name] = point["step_s"]
                heads[name] = point["events_head"]
    except Exception as e:  # noqa: BLE001 — an unusable loopback box
        return [{"metric": "events_overhead",
                 "error": f"{type(e).__name__}: {e}"}]
    overhead = (best["on"] - best["off"]) / best["off"] * 100.0
    return [{
        "metric": "events_overhead",
        "ranks": ranks, "tensors_per_step": tensors,
        "elems_per_tensor": elems,
        "step_s_events_on": round(best["on"], 6),
        "step_s_events_off": round(best["off"], 6),
        "overhead_pct": round(overhead, 3),
        "events_recorded": heads["on"],
        "criterion": "overhead_pct < 2 (ungrouped eager lane, "
                     "best-of-%d)" % repeats,
        "pass": overhead < 2.0,
    }]


def _serving_rows():
    """Serving-lane rows (docs/serving.md): sustained tok/s and
    p50/p99 request latency of the continuous-batching decode engine
    under a seeded Poisson arrival trace, one row per paged-KV block
    format (f32 / int8), plus the `serving_trace_overhead` row
    (request-tracing on vs off on the closed-loop decode lane; the
    < 2% criterion mirrors --events-overhead). Runs
    horovod_tpu/serving/bench_lane.py as a CPU-pinned SUBPROCESS —
    substrate-independent like ring_busbw."""
    import os
    import subprocess

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.dirname(os.path.abspath(__file__))
                + os.pathsep + env.get("PYTHONPATH", "")})
    try:
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.serving.bench_lane"],
            capture_output=True, text=True, timeout=600, env=env,
            check=True)
    except Exception as e:  # noqa: BLE001 — a failed serving lane
        # yields an error row; the rest of the bench run continues.
        detail = getattr(e, "stderr", "") or ""
        return [{"metric": "serving_latency",
                 "error": f"{type(e).__name__}: {e} {detail[-400:]}"}]
    rows = []
    for line in out.stdout.splitlines():
        if line.startswith("SERVING_ROW "):
            rows.append(json.loads(line.split(" ", 1)[1]))
    if not rows:
        return [{"metric": "serving_latency",
                 "error": "bench_lane emitted no rows",
                 "tail": out.stdout[-400:]}]
    return rows


# Child body for one ring_busbw rank: pure host — numpy + the native
# core over TCP loopback, no jax import.
# Alongside the end-to-end busbw (the NCCL-tests convention: includes
# negotiation, queueing, and the API path), each point reports
# `wire_gbps` — the same bus formula over the TRANSPORT time alone
# (the core's wire_us histogram delta), which is what the striped
# multi-channel engine actually moves; on a loopback box the fixed
# per-op API overhead (~5 ms) otherwise dilutes the transport win at
# large payloads. Warmup is 3 ops and large sizes run >= 6 timed
# iterations: the first ops after connect pay TCP ramp + page faults
# and a 2-iteration sample was dominated by them.
_RING_BUSBW_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
from horovod_tpu.common import basics, eager_ops
b = basics.HorovodBasics()
b.init()
rank, size = b.rank(), b.size()
points = []
try:
    for nbytes in json.loads(os.environ["RING_BUSBW_SIZES"]):
        elems = max(nbytes // 4, 1)
        x = np.full(elems, float(rank + 1), np.float32)
        iters = max(6, min(20, (1 << 26) // max(nbytes, 1)))
        for w in range(3):
            eager_ops.allreduce_async(x, f"bw.{nbytes}.w{w}").synchronize()
        snap0 = b.metrics_snapshot()
        t0 = time.perf_counter()
        for i in range(iters):
            eager_ops.allreduce_async(x, f"bw.{nbytes}.{i}").synchronize()
        dt = (time.perf_counter() - t0) / iters
        snap1 = b.metrics_snapshot()
        tx = snap1["wire"]["tx_bytes"] - snap0["wire"]["tx_bytes"]
        txl = (snap1["wire"]["tx_logical_bytes"]
               - snap0["wire"]["tx_logical_bytes"])
        wire_dt = (snap1["wire_us"]["sum_us"]
                   - snap0["wire_us"]["sum_us"]) / iters / 1e6
        bus = 2 * (size - 1) / size * nbytes
        points.append({
            "payload_bytes": nbytes,
            "busbw_gbps": round(bus / dt / 1e9, 4),
            "wire_gbps": round(bus / wire_dt / 1e9, 4) if wire_dt else None,
            "step_s": round(dt, 6),
            "wire_ratio": round(tx / txl, 4) if txl else None,
        })
finally:
    b.shutdown()
if rank == 0:
    print("RING_BUSBW_POINTS " + json.dumps(points), flush=True)
"""


def _run_loopback_ranks(child_src, sentinel, ranks, env_extra,
                        timeout=600):
    """Spawn ``ranks`` local subprocesses wired as ONE Horovod job over
    a fresh loopback port, run ``child_src`` in each, and return rank
    0's ``sentinel``-prefixed JSON payload. The shared launcher behind
    both subprocess-grid benches (`ring_busbw`, `zero_sweep`) — one
    place for the port probe, env plumbing, drain, and kill-on-error."""
    import os
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = os.path.dirname(os.path.abspath(__file__))
    procs = []
    try:
        for r in range(ranks):
            env = dict(os.environ)
            env.update({
                "HOROVOD_RANK": str(r), "HOROVOD_SIZE": str(ranks),
                "HOROVOD_LOCAL_RANK": str(r),
                "HOROVOD_LOCAL_SIZE": str(ranks),
                "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
                "HOROVOD_CONTROLLER_PORT": str(port),
                "HVDTPU_REPO": repo,
            })
            env.update(env_extra)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", child_src],
                stdout=subprocess.PIPE if r == 0 else subprocess.DEVNULL,
                stderr=subprocess.DEVNULL, text=True, env=env))
        out, _ = procs[0].communicate(timeout=timeout)
        for p in procs[1:]:
            p.wait(timeout=60)
        payload = None
        for line in out.splitlines():
            if line.startswith(sentinel + " "):
                payload = json.loads(line.split(" ", 1)[1])
        if payload is None:
            raise RuntimeError(f"rank 0 emitted no {sentinel}")
        return payload
    except Exception:
        for p in procs:
            p.kill()
        raise


# Child body for one hier_busbw rank: like the ring child, but the
# worker first overrides its layout env to the emulated 2-slice
# topology (HIER_LOCAL ranks per slice) and additionally reports the
# cross-plane wire counters the hierarchical decomposition books.
_HIER_BUSBW_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
L = int(os.environ["HIER_LOCAL"])
rank = int(os.environ["HOROVOD_RANK"])
size = int(os.environ["HOROVOD_SIZE"])
os.environ.update({
    "HOROVOD_LOCAL_RANK": str(rank % L),
    "HOROVOD_LOCAL_SIZE": str(L),
    "HOROVOD_CROSS_RANK": str(rank // L),
    "HOROVOD_CROSS_SIZE": str(size // L),
})
from horovod_tpu.common import basics, eager_ops
b = basics.HorovodBasics()
b.init()
points = []
try:
    for nbytes in json.loads(os.environ["RING_BUSBW_SIZES"]):
        elems = max(nbytes // 4, 1)
        x = np.full(elems, float(rank + 1), np.float32)
        iters = max(2, min(20, (1 << 24) // nbytes))
        eager_ops.allreduce_async(x, f"bw.{nbytes}.warm").synchronize()
        snap0 = b.metrics_snapshot()["wire"]
        t0 = time.perf_counter()
        for i in range(iters):
            eager_ops.allreduce_async(x, f"bw.{nbytes}.{i}").synchronize()
        dt = (time.perf_counter() - t0) / iters
        snap1 = b.metrics_snapshot()["wire"]
        d = lambda k: snap1[k] - snap0[k]
        # Flat-ring DCN baseline: the locality-blind flat ring streams
        # 2(N-1)/N x payload per rank with no idea where the slice
        # boundary is, so all of it prices at DCN rates.
        flat_dcn = 2 * (size - 1) * nbytes
        cross = d("cross_tx_bytes") / iters
        points.append({
            "payload_bytes": nbytes,
            "busbw_gbps": round(2 * (size - 1) / size * nbytes / dt / 1e9,
                                4),
            "step_s": round(dt, 6),
            "wire_ratio": (round(d("tx_bytes") / d("tx_logical_bytes"), 4)
                           if d("tx_logical_bytes") else None),
            "cross_bytes_per_iter": int(cross),
            "cross_ratio_vs_flat": round(size * cross / flat_dcn, 4),
        })
finally:
    b.shutdown()
if rank == 0:
    print("HIER_BUSBW_POINTS " + json.dumps(points), flush=True)
"""


def _hier_busbw_rows(ranks=4, local=2):
    """Cross-plane allreduce bus-bandwidth sweep at an emulated
    ``ranks/local`` slices x ``local`` ranks topology: flat host ring
    vs hierarchical vs hierarchical with the bf16 codec on the
    cross-plane hop (docs/redistribute.md). ``cross_ratio_vs_flat`` is
    the world cross-plane tx bytes over the locality-blind flat ring's
    full stream — the ISSUE-8 acceptance wants <= ~(1/local + eps) at
    16 MiB on the hier rows (the bf16 row halves it again)."""
    sizes = [1 << 15, 1 << 20, 1 << 24]
    configs = [
        ("flat", {"HOROVOD_CROSS_PLANE": "ring"}),
        ("hier", {"HOROVOD_CROSS_PLANE": "hier"}),
        ("hier+bf16-cross", {"HOROVOD_CROSS_PLANE": "hier",
                             "HOROVOD_CROSS_PLANE_COMPRESSION": "1"}),
    ]
    rows = []
    for name, knobs in configs:
        row = {"metric": "hier_busbw", "config": name, "ranks": ranks,
               "slices": ranks // local,
               "unit": "host allreduce bus GB/s at an emulated "
                       f"{ranks // local}x{local} topology; "
                       "cross_ratio_vs_flat = world cross-plane tx / "
                       "flat-ring full stream"}
        try:
            row["points"] = _run_loopback_ranks(
                _HIER_BUSBW_CHILD, "HIER_BUSBW_POINTS", ranks,
                dict(knobs, HIER_LOCAL=str(local),
                     RING_BUSBW_SIZES=json.dumps(sizes)))
        except Exception as e:  # noqa: BLE001 — a failed transport
            # config yields an error row; the sweep continues.
            row["error"] = f"{type(e).__name__}: {e}"
        rows.append(row)
    return rows


def _ring_busbw_rows(ranks=4):
    """Host-ring allreduce bus-bandwidth sweep, one JSON row per
    transport config: bulk-synchronous (chunk knob 0 — the pre-r10
    engine), chunk-overlapped (default 256 KiB double-buffered
    pipeline), chunk-overlapped + bf16 wire compression, and the
    multi-channel striped transport (HOROVOD_WIRE_CHANNELS=K: chunk i
    rides socket i % K with one reduce worker per channel) at K in
    {2, 4}. Every row carries its ``channels`` so perfwatch series
    never cross-join K=1 and K=4 (ROW_IDENTITY_FIELDS). The striped
    win is per-LINK parallelism, and a loopback box saturates its
    aggregate fabric with >= 4 ranks pumping — so the sweep adds a
    2-rank lane (K=1 vs K=4) where the per-link headroom is visible;
    the `wire_gbps` column (transport time alone) is the striping
    acceptance number, busbw the end-to-end one. 1 KiB to 64 MiB
    payloads over local processes on TCP loopback —
    substrate-independent, so the driver's bench capture gets the
    overlap, compression, and striping wins as numbers on any box.
    busbw follows the NCCL-tests convention (2(N-1)/N x payload /
    time); wire_ratio is the measured transport/full-width byte
    quotient (~0.5 when bf16 engages — the core's wire-vs-logical
    counters)."""
    sizes = [1 << 10, 1 << 15, 1 << 20, 1 << 24, 1 << 26]
    unit = ("host-ring allreduce bus GB/s (2(N-1)/N x payload/time), "
            "TCP loopback; wire_gbps = same formula over transport "
            "(wire_us) time; wire_ratio = transport/full-width bytes")
    configs = [
        ("bulk", ranks, 1, {"HOROVOD_RING_CHUNK_BYTES": "0",
                            "HOROVOD_WIRE_COMPRESSION": "0"}),
        ("overlap", ranks, 1,
         {"HOROVOD_RING_CHUNK_BYTES": str(256 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "0"}),
        ("overlap+bf16", ranks, 1,
         {"HOROVOD_RING_CHUNK_BYTES": str(256 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "1"}),
        # Striped lanes: 1 MiB chunks (each channel still cuts multi-
        # chunk streams at 16 MiB), uncompressed — the pure transport
        # comparison against `overlap`.
        ("striped-k2", ranks, 2,
         {"HOROVOD_RING_CHUNK_BYTES": str(1024 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "0",
          "HOROVOD_WIRE_CHANNELS": "2"}),
        ("striped-k4", ranks, 4,
         {"HOROVOD_RING_CHUNK_BYTES": str(1024 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "0",
          "HOROVOD_WIRE_CHANNELS": "4"}),
        # Per-link lane: 2 ranks, where loopback aggregate bandwidth
        # does not mask the per-pair stripe win (K=1 baseline + K=4).
        ("overlap-n2", 2, 1,
         {"HOROVOD_RING_CHUNK_BYTES": str(256 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "0"}),
        ("striped-k4-n2", 2, 4,
         {"HOROVOD_RING_CHUNK_BYTES": str(1024 * 1024),
          "HOROVOD_WIRE_COMPRESSION": "0",
          "HOROVOD_WIRE_CHANNELS": "4"}),
    ]
    rows = []
    for name, nranks, channels, knobs in configs:
        row = {"metric": "ring_busbw", "config": name, "ranks": nranks,
               "channels": channels, "unit": unit}
        try:
            row["points"] = _run_loopback_ranks(
                _RING_BUSBW_CHILD, "RING_BUSBW_POINTS", nranks,
                dict(knobs, RING_BUSBW_SIZES=json.dumps(sizes)))
        except Exception as e:  # noqa: BLE001 — a failed transport
            # config yields an error row; the sweep continues.
            row["error"] = f"{type(e).__name__}: {e}"
        rows.append(row)
    return rows


# Child body for one zero_sweep rank: jax pinned to CPU (subprocess, so
# the parent's device heap is untouched), the eager ZeRO lane against
# its replicated baseline at a synthetic ~8 MB f32 geometry.
_ZERO_SWEEP_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu.jax as hvd
from horovod_tpu.jax.compression import Compression
from horovod_tpu.parallel.zero import (
    optimizer_state_bytes, zero_bucket_layout)
from horovod_tpu.telemetry.predict import zero_layout_bytes

knobs = json.loads(os.environ["ZERO_SWEEP_KNOBS"])
steps = knobs["steps"]
hvd.init()
rank, size = hvd.rank(), hvd.size()
# ~2M f32 elements over a dozen leaves (layer-ish shapes, one ragged).
shapes = [(512, 256)] * 8 + [(256, 512)] * 6 + [(4099,), (257,)]
params = {f"p{i}": jnp.zeros(s, jnp.float32) + 0.1 * i
          for i, s in enumerate(shapes)}
grads = {f"p{i}": jnp.full(s, 0.01 * ((rank + i) % 5 - 2), jnp.float32)
         for i, s in enumerate(shapes)}
n_elems = sum(int(np.prod(s)) for s in shapes)
if knobs["zero"]:
    opt = hvd.DistributedFusedAdam(
        1e-3, zero=True, bucket_bytes=knobs["bucket_bytes"],
        overlap=knobs["overlap"],
        compression=getattr(Compression, knobs["compression"]))
    layout = zero_bucket_layout(list(params.values()), size,
                                knobs["bucket_bytes"])
    if knobs["compression"] == "bf16":
        # The param allgather's LOGICAL payload is genuinely bf16 wide
        # (the op ships a 2-byte tensor); only the reduce-scatter stays
        # f32-logical (bf16 on the wire rides below the op accounting).
        predicted = sum(b.padded * (4 + 2) for b in layout.buckets)
    else:
        predicted = zero_layout_bytes(layout)
else:
    opt = hvd.DistributedFusedAdam(1e-3)
    # allreduce logical volume per step: the full gradient tree.
    predicted = n_elems * 4
state = opt.init(params)
try:
    params, state = opt.apply(params, grads, state)  # warm (compiles)
    from horovod_tpu import telemetry
    snap0 = telemetry.snapshot()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, state = opt.apply(params, grads, state)
    dt = (time.perf_counter() - t0) / steps
    snap1 = telemetry.snapshot()
    wire = (snap1["wire"]["tx_bytes"] - snap0["wire"]["tx_bytes"]) / steps
    ops = 0
    for op_name in ("allreduce", "reducescatter", "allgather"):
        ops += (snap1["ops"].get(op_name, {}).get("bytes", 0)
                - snap0["ops"].get(op_name, {}).get("bytes", 0))
    ops /= steps
    row = {
        "step_s": round(dt, 6),
        "per_rank_opt_bytes": optimizer_state_bytes(state),
        "param_bytes": n_elems * 4,
        "wire_tx_bytes_per_step": wire,
        "ops_logical_bytes_per_step": ops,
        "predicted_logical_bytes": predicted,
        "byte_reconciliation": round(ops / predicted, 4) if predicted
        else None,
    }
finally:
    hvd.shutdown()
if rank == 0:
    print("ZERO_SWEEP_ROW " + json.dumps(row), flush=True)
"""


def _zero_sweep_rows(ranks=4, steps=5):
    """The zero on/off x bucket-size tuning grid (`zero_sweep` JSON
    rows): the eager replicated-allreduce baseline vs ZeRO-1 sharded
    (phase-separated), ZeRO-1 overlapped (per-bucket reduce-scatter /
    allgather pipelined under the shard updates), and overlapped +
    bf16 wire (compressed reduce-scatter in the core + bf16 param
    allgather) — each zero config at two bucket granularities. Local
    CPU subprocesses over TCP loopback, so the grid runs on any box;
    rows carry per-rank optimizer bytes (the N-fold ZeRO-1 cut), step
    time (the overlap win), measured wire bytes (the ~0.5x compressed
    quotient vs the allreduce baseline), and the predicted-vs-measured
    logical-byte reconciliation (docs/zero.md)."""
    bucket_grid = [256 * 1024, 4 * 1024 * 1024]
    configs = [("replicated", {"zero": False}, None)]
    for bb in bucket_grid:
        configs += [
            ("zero1", {"zero": True, "overlap": False}, bb),
            ("zero1+overlap", {"zero": True, "overlap": True}, bb),
            ("zero1+overlap+bf16",
             {"zero": True, "overlap": True, "compression": "bf16",
              "wire": "1"}, bb),
        ]
    rows, base_wire = [], None
    for name, knobs, bb in configs:
        payload = {"zero": knobs.get("zero", False),
                   "overlap": knobs.get("overlap", False),
                   "compression": knobs.get("compression", "none"),
                   "bucket_bytes": bb or 0, "steps": steps}
        row = {"metric": "zero_sweep", "config": name, "ranks": ranks,
               "bucket_bytes": bb,
               "unit": "eager optimizer lane over TCP loopback; wire = "
                       "transport tx bytes/step (hvd.metrics), "
                       "reconciliation = ops-logical vs layout-"
                       "predicted bytes"}
        try:
            row.update(_run_loopback_ranks(
                _ZERO_SWEEP_CHILD, "ZERO_SWEEP_ROW", ranks,
                {"HOROVOD_WIRE_COMPRESSION": knobs.get("wire", "0"),
                 "JAX_PLATFORMS": "cpu",
                 "ZERO_SWEEP_KNOBS": json.dumps(payload)}))
            if name == "replicated":
                base_wire = row["wire_tx_bytes_per_step"]
            if base_wire:
                row["wire_ratio_vs_replicated"] = round(
                    row["wire_tx_bytes_per_step"] / base_wire, 4)
        except Exception as e:  # noqa: BLE001 — a failed grid point
            # yields an error row; the sweep continues.
            row["error"] = f"{type(e).__name__}: {e}"
        rows.append(row)
    return rows


# Child body for one jit_fusion rank: the host-lane fused train step
# (hvd.make_fused_train_step — segmented backward jits, per-bucket
# reduce-scatters fired at segment boundaries, allgathers deferred
# into the next step) vs the bulk-synchronous unfused schedule the
# HOROVOD_JIT_FUSION=0 escape hatch restores. A StepTimer brackets
# every step so the core's overlap ledger attributes exposed/hidden
# wire time per plane (docs/metrics.md), and each rank dumps its event
# ring for the parent's critical-path attribution.
_FUSION_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu.jax as hvd
from horovod_tpu.parallel.zero import zero_bucket_layout
from horovod_tpu.telemetry import critpath
from horovod_tpu.telemetry.step_timer import StepTimer

knobs = json.loads(os.environ["FUSION_KNOBS"])
steps, width, depth = knobs["steps"], knobs["width"], knobs["depth"]
hvd.init()
rank, size = hvd.rank(), hvd.size()
key = jax.random.PRNGKey(0)
params = {}
for i in range(depth):
    key, k = jax.random.split(key)
    params[f"w{i}"] = (jax.random.normal(k, (width, width))
                       / np.sqrt(width)).astype(jnp.float32)

def loss_fn(p, batch):
    h = batch["x"]
    for i in range(depth):
        h = jnp.tanh(h @ p[f"w{i}"])
    return jnp.mean((h - batch["y"]) ** 2)

batch = {"x": jax.random.normal(jax.random.PRNGKey(1),
                                (knobs["batch"], width)),
         "y": jax.random.normal(jax.random.PRNGKey(2),
                                (knobs["batch"], width))}
n_buckets = len(zero_bucket_layout(list(params.values()), size,
                                   knobs["bucket_bytes"]).buckets)
# The knob under test rides in via HOROVOD_JIT_FUSION (the env
# escape hatch itself, not set_jit_fusion — the bench exercises the
# operator-facing path).
init, step, finish = hvd.make_fused_train_step(
    loss_fn, 1e-3, bucket_bytes=knobs["bucket_bytes"])
carry = init(params)
timer = StepTimer()
try:
    loss, carry = step(carry, batch)  # warm: compiles every segment
    for _ in range(steps):
        timer.start_step()
        loss, carry = step(carry, batch)
        timer.end_step(loss)
    _, carry = finish(carry)
    ov = timer.overlap_summary() or {}
    intra = ov.get("intra", {})
    row = {
        "step_s": round(timer.mean_step_s(), 6),
        "n_buckets": n_buckets,
        "overlap_efficiency": round(ov.get("overlap_efficiency", 0.0),
                                    4),
        "mean_exposed_wire_ms": round(
            intra.get("mean_exposed_wire_ms", 0.0), 3),
        "mean_hidden_wire_ms": round(
            intra.get("mean_hidden_wire_ms", 0.0), 3),
        "mean_total_wire_ms": round(
            intra.get("mean_total_wire_ms", 0.0), 3),
    }
    dump = os.environ.get("FUSION_DUMP_DIR")
    if dump:
        critpath.write_event_dump(
            os.path.join(dump, f"blackbox-rank{rank}.jsonl"),
            rank, size, hvd.events())
finally:
    hvd.shutdown()
if rank == 0:
    print("JIT_FUSION_ROW " + json.dumps(row), flush=True)
"""


def _fusion_rows(ranks=2, steps=6):
    """The jit-lane compute/collective fusion rows (`jit_fusion`):
    the fused host-lane step (per-bucket reduce-scatters interleaved
    with the segmented backward, allgathers hidden under the next
    step's forward) vs the unfused bulk-synchronous schedule
    (`HOROVOD_JIT_FUSION=0`), 2 CPU loopback ranks. The headline
    column is the overlap ledger's ``overlap_efficiency`` — ~0 was
    the whole jit lane's value before the fusion work (every byte
    moved while the host sat between programs); perfwatch watches it
    (down = regression) like any other bench series. Each config also
    runs the critical-path attribution over the ranks' event dumps
    (`report.py --critical-path` on the same files): the acceptance
    signal is the blocking phase moving OFF wire on the fused config
    (docs/fusion.md)."""
    import shutil
    import tempfile

    from horovod_tpu.telemetry import critpath

    # Wire-heavy geometry on purpose (18 MB of params, small batch):
    # the schedule contrast shows in the ledger — bulk-synchronous
    # exposes ~20 ms of wire per step here, the fused schedule ~4 ms.
    # step_s is NOT the signal on this substrate: the loopback "wire"
    # is the same cores as the compute, so hidden wire doesn't come
    # free the way an independently-draining NIC/ICI makes it on TPU.
    payload = {"steps": steps, "width": 768, "depth": 8, "batch": 4,
               "bucket_bytes": 512 * 1024}
    rows = []
    for name, knob in (("unfused", "0"), ("fused", "1")):
        row = {"metric": "jit_fusion", "config": name, "ranks": ranks,
               "bucket_bytes": payload["bucket_bytes"],
               "unit": "host-lane fused train step over TCP loopback; "
                       "overlap_efficiency = hidden/total wire time "
                       "from the step-window overlap ledger; "
                       "blocking_phase from the cross-rank "
                       "critical-path attribution"}
        dump = tempfile.mkdtemp(prefix=f"hvd-fusion-{name}-")
        try:
            row.update(_run_loopback_ranks(
                _FUSION_CHILD, "JIT_FUSION_ROW", ranks,
                {"JAX_PLATFORMS": "cpu",
                 "HOROVOD_JIT_FUSION": knob,
                 "FUSION_DUMP_DIR": dump,
                 "FUSION_KNOBS": json.dumps(payload)}))
            analysis = critpath.critical_path(dump)
            pc = analysis.get("phase_counts", {})
            if pc:
                row["blocking_phase"] = max(pc, key=pc.get)
                row["phase_counts"] = pc
        except Exception as e:  # noqa: BLE001 — a failed config yields
            # an error row; the other config still measures.
            row["error"] = f"{type(e).__name__}: {e}"
        finally:
            shutil.rmtree(dump, ignore_errors=True)
        rows.append(row)
    return rows


def _fleet_util_rows(world_sizes=(64, 256), steps=8):
    """The fleet rank-seconds aggregation rows (`fleet_utilization`,
    docs/fleet.md; no accelerator needed): synthesize a simworld fleet
    with one straggler plus the full r23 evidence surface (wait blocks,
    serving request lifecycles, a recorded SLO breach), run the
    post-mortem fleet analysis over every rank's dump, and emit one row
    per world size. Watched columns: ``utilization`` (down =
    regression), ``unattributed_share`` (the ledger losing evidence),
    ``breaches`` (count growing), and ``analyze_s`` — the aggregation
    itself must stay interactive at 256 ranks (< 2 s acceptance bar)."""
    import shutil
    import tempfile

    from horovod_tpu.simworld import harness
    from horovod_tpu.telemetry import fleet

    rows = []
    for ranks in world_sizes:
        row = {"metric": "fleet_utilization", "config": "simworld",
               "ranks": ranks, "steps": steps,
               "unit": "rank-seconds ledger over synthesized per-rank "
                       "dumps (one straggler, fused-lane waits, one "
                       "serving request per step, one recorded "
                       "breach); utilization = attributed useful share "
                       "of every rank's window"}
        out = tempfile.mkdtemp(prefix=f"hvd-fleet-{ranks}-")
        try:
            harness.write_sim_step_dumps(
                out, ranks=ranks, steps=steps, slow_rank=ranks // 3,
                waits=True, serving=True,
                breach={"objective": 4, "rank": ranks // 3,
                        "value": 750, "phase": 6,
                        "objective_name": "stall_ms",
                        "phase_name": "stall"})
            t0 = time.perf_counter()
            analysis = fleet.analyze(out)
            dt = time.perf_counter() - t0
            f = analysis["fleet"]
            total_us = f["window_us"]
            row.update({
                "utilization": f["utilization"],
                "unattributed_share": round(
                    f["rank_seconds"]["unattributed"] * 1e6
                    / total_us, 6) if total_us else 0.0,
                "breaches": len(analysis["slo"]["breach_events"]),
                "worst_rank": f["worst_rank"],
                "analyze_s": round(dt, 4),
            })
        except Exception as e:  # noqa: BLE001 — a failed size yields
            # an error row; the other sizes still measure.
            row["error"] = f"{type(e).__name__}: {e}"
        finally:
            shutil.rmtree(out, ignore_errors=True)
        rows.append(row)
    return rows


def _sweep_points(batch):
    """The --sweep point table: (name, config, run_spmd kwargs)."""
    import dataclasses

    fc = _flagship_cfg()
    return [
        ("update-split-b4", fc, dict()),
        ("update-fused-b4", fc, dict(update="fused")),
        # Microbatch-accumulation lane: N-way accumulation at N-x batch
        # keeps the per-microbatch activation footprint of b4 while
        # amortizing the optimizer-apply pass over more tokens.
        ("fused-b8-accum2", fc,
         dict(update="fused", microbatches=2, batch=2 * batch)),
        ("fused-b16-accum4", fc,
         dict(update="fused", microbatches=4, batch=4 * batch)),
        ("remat-attn", dataclasses.replace(fc, remat="attn"), dict()),
        # attn+gate+qkv exceeded HBM monolithically at b4 (r5); under
        # 2-way accumulation the halved activation stash may fit.
        ("remat-attn+gate+qkv-accum2",
         dataclasses.replace(fc, remat="attn+gate+qkv"),
         dict(update="fused", microbatches=2)),
        ("flash-block-512", dataclasses.replace(fc, flash_block=512),
         dict(update="fused")),
        ("flash-block-2048", dataclasses.replace(fc, flash_block=2048),
         dict(update="fused")),
    ]


def _bubble_rows(S=4, microbatches=(8, 16), virtual=(1, 2, 4)):
    """One JSON row per (schedule, V, accum) pipeline point — the
    schedule-derived bubble fraction at ``S`` stages, straight from the
    slot tables the implementation executes, so the driver's bench
    capture can diff schedules without parsing prose. Pure host math:
    emitted by --sweep on ANY substrate (a single chip cannot raise a
    pipe axis, so these are the pipeline lane's portable numbers; the
    gradient equivalence behind them is pinned by
    tests/single/test_pipeline_interleaved.py).

    gpipe / lockstep-1f1b use their closed forms (in fwd+bwd subtick
    units, matching the interleaved engine's accounting); interleaved
    rows come from parallel.pipeline.build_interleaved_schedule.
    """
    from horovod_tpu.parallel.pipeline import build_interleaved_schedule

    rows = []

    def row(schedule, V, M, bubble, slots):
        return {
            "metric": "pipeline_bubble",
            "schedule": schedule, "V": V, "accum": M, "S": S,
            "slots": slots, "value": round(bubble, 4),
            "unit": f"idle fraction of fwd+bwd subticks, S={S} stages, "
                    f"M={M} microbatches, V={V} virtual chunks/device",
        }

    for M in microbatches:
        rows.append(row("gpipe", 1, M,
                        2 * (S - 1) / (2 * M + 2 * (S - 1)),
                        2 * (M + S - 1)))
        rows.append(row("1f1b", 1, M,
                        2 * (S - 1) / (M + 2 * (S - 1)),
                        2 * (M + 2 * (S - 1))))
        for V in virtual:
            s = build_interleaved_schedule(S, V, M)
            rows.append(row("interleaved_1f1b", V, M,
                            s.bubble_fraction, s.n_slots))
    return rows


def _run_sweep_point(name, batch, seq, steps, emit):
    """Measure ONE sweep point in THIS process (`--sweep-point NAME`,
    spawned by --sweep). Every row carries explicit (schedule, V,
    accum) fields so schedule diffs are machine-readable."""
    for pname, cfg, kw in _sweep_points(batch):
        if pname == name:
            b = kw.pop("batch", batch)
            row = run_spmd(cfg, b, seq, steps,
                           f"llama_sweep_{name}", name, **kw)
            row.update(schedule="none", V=1,
                       accum=kw.get("microbatches", 1))
            emit(row)
            return
    raise SystemExit(f"unknown sweep point {name!r}")


def _run_sweep(batch, seq, steps, emit):
    """On-chip tuning lane (`bench.py --sweep`, NOT part of the driver
    run): update formulation, microbatch accumulation, remat save-set,
    and flash (qkv-attention) block shapes at the flagship geometry.
    One JSON row per point, each measured in its OWN child process, so
    a crashing or hanging point yields an error row and the sweep
    continues. (The parent stays off jax: a chip belongs to one process
    at a time.)"""
    import os
    import subprocess

    for name, _cfg, _kw in _sweep_points(batch):
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--sweep-point", name],
                capture_output=True, text=True, timeout=1500)
            row = None
            for line in out.stdout.strip().splitlines():
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
            if row is None or out.returncode != 0:
                tail = (out.stderr or out.stdout).strip()[-300:]
                row = {"metric": f"llama_sweep_{name}",
                       "error": f"rc={out.returncode}: {tail}"}
        except subprocess.TimeoutExpired:
            row = {"metric": f"llama_sweep_{name}",
                   "error": "HUNG: no result within 1500 s"}
        emit(row)


# ---- bench-row diffing (`bench.py --diff old.json new.json`) ----------
# Load two row files (bench JSONL, a JSON array, or a driver artifact
# whose `tail` embeds rows), match rows by their identity fields, and
# print a per-row delta table over every numeric measurement field.

# Fields that are neither identity nor comparable measurements. The
# identity (join-key) field list is shared with perfwatch
# (ROW_IDENTITY_FIELDS) so grouping and diffing can never disagree.
_DIFF_SKIP_FIELDS = {"schema", "unit", "error", "ts", "wall_s", "tail"}


def _diff_key(row, seen, key_fields):
    key = tuple((f, row.get(f)) for f in key_fields if f in row)
    n = seen.get(key, 0)
    seen[key] = n + 1
    return key + (("occurrence", n),) if n else key


def _diff_rows(old_path, new_path, threshold=0.0):
    """Compare two bench row files; returns (lines, worst_rel_change).
    Refuses mismatched `schema` stamps — a renamed column diffed by
    name is a silent lie, so format drift must fail loudly. Rows with a
    nested `points` list (ring_busbw/hier_busbw) are flattened to one
    pseudo-row per point first, so the per-size busbw measurements diff
    like any other field."""
    from horovod_tpu.telemetry.perfwatch import (
        ROW_IDENTITY_FIELDS,
        check_schema,
        flatten_rows,
        load_rows,
    )

    old_rows, new_rows = load_rows(old_path), load_rows(new_path)
    old_schema = check_schema(old_rows, what=old_path)
    new_schema = check_schema(new_rows, what=new_path)
    if old_schema != new_schema:
        raise SystemExit(
            f"bench --diff: refusing to compare schema {old_schema} "
            f"({old_path}) against schema {new_schema} ({new_path}) — "
            "row formats differ; re-run the older side on this tree")
    seen_old, seen_new = {}, {}
    old_by_key = {_diff_key(r, seen_old, ROW_IDENTITY_FIELDS): r
                  for r in flatten_rows(old_rows)}
    new_by_key = {_diff_key(r, seen_new, ROW_IDENTITY_FIELDS): r
                  for r in flatten_rows(new_rows)}
    lines = [f"{'row':<52} {'field':<24} {'old':>12} {'new':>12} "
             f"{'delta':>9}"]
    worst = 0.0
    for key in old_by_key:
        if key not in new_by_key:
            lines.append(f"{_key_str(key):<52} (only in {old_path})")
            continue
        old, new = old_by_key[key], new_by_key[key]
        for field in sorted(set(old) & set(new)):
            ov, nv = old[field], new[field]
            if (field in _DIFF_SKIP_FIELDS
                    or any(f == field for f, _ in key)
                    or not isinstance(ov, (int, float))
                    or not isinstance(nv, (int, float))
                    or isinstance(ov, bool) or isinstance(nv, bool)):
                continue
            if ov:
                rel = (nv - ov) / abs(ov)
                delta = f"{rel:>+8.1%}"
            elif nv:
                # 0 -> x has no finite relative change: shown, never
                # threshold-dropped, and it moves the worst tally (a
                # counter appearing — crc_errors, stalls — IS news).
                rel = None
                delta = "    (new)"
            else:
                rel = 0.0
                delta = f"{0.0:>+8.1%}"
            if rel is not None and abs(rel) < threshold:
                continue
            worst = max(worst, abs(rel) if rel is not None else 1.0)
            lines.append(f"{_key_str(key):<52} {field:<24} "
                         f"{ov:>12.6g} {nv:>12.6g} {delta}")
    for key in new_by_key:
        if key not in old_by_key:
            lines.append(f"{_key_str(key):<52} (only in {new_path})")
    return lines, worst


def _key_str(key):
    return "/".join(str(v) for _, v in key if v is not None)


def main():
    argv = sys.argv[1:]
    batch, seq, steps = _BENCH_SHAPE

    def emit(row):
        # Print each row AS PRODUCED: a later config failing must not
        # discard minutes of already-measured rows. gc between rows
        # returns every stale device buffer before the next config
        # allocates. A list is several rows (run_eager yields its
        # telemetry goodput row alongside the MFU headline). Every row
        # is stamped with the format version HERE — one choke point —
        # so --diff/perfwatch schema guards see a uniform stamp.
        for r in (row if isinstance(row, list) else [row]):
            r.setdefault("schema", BENCH_SCHEMA)
            print(json.dumps(r), flush=True)
        gc.collect()

    if "--diff" in argv:
        # Two-point trajectory comparison (no accelerator needed):
        # per-row delta table between any two bench row files.
        # --diff-threshold 0.05 hides deltas under 5% (0->x rows are
        # always shown — no finite relative change to threshold).
        i = argv.index("--diff")
        try:
            old_path, new_path = argv[i + 1], argv[i + 2]
        except IndexError:
            raise SystemExit("usage: bench.py --diff old.json new.json "
                             "[--diff-threshold 0.05]")
        threshold = 0.0
        if "--diff-threshold" in argv:
            threshold = float(argv[argv.index("--diff-threshold") + 1])
        lines, worst = _diff_rows(old_path, new_path,
                                  threshold=threshold)
        for line in lines:
            print(line)
        print(f"bench --diff: worst relative change {worst:+.1%}")
        return
    if "--lint" in argv:
        # hvdlint preflight: statically analyze every shipped program
        # (collective divergence, axis validity, donation hazards,
        # pipeline schedule conformance — docs/analysis.md) BEFORE
        # committing chip-hours. Exit nonzero on any error diagnostic;
        # a 256-chip deadlock this catches costs seconds here.
        from horovod_tpu.analysis.lint import main as lint_main

        rc = lint_main(["--all"])
        if rc != 0:
            sys.exit(rc)
        argv = [a for a in argv if a != "--lint"]
        if not argv:
            return
    if "--events-overhead" in argv:
        # Standalone event-ring overhead check (no accelerator needed):
        # the ungrouped eager lane with the flight recorder on vs off.
        for row in _events_overhead_rows():
            emit(row)
        return
    if "--scale" in argv:
        # Standalone control-plane scaling curves (no accelerator):
        # the full 8..256 ladder, flat star vs tree gather.
        for row in _control_plane_scaling_rows():
            emit(row)
        return
    if "--serving" in argv:
        # Standalone serving lane (no accelerator needed): the
        # continuous-batching decode engine under a Poisson trace,
        # f32 and int8 paged-KV rows + the request-tracing overhead
        # check (serving_trace_overhead, < 2% criterion).
        for row in _serving_rows():
            emit(row)
        return
    if "--ring-busbw" in argv:
        # Standalone host-ring transport sweep (no accelerator needed),
        # including the cross-plane hierarchical rows (dense/hier lane).
        for row in _ring_busbw_rows():
            emit(row)
        for row in _hier_busbw_rows():
            emit(row)
        return
    if "--zero-sweep" in argv:
        # Standalone ZeRO grid (CPU loopback subprocesses; any box).
        for row in _zero_sweep_rows():
            emit(row)
        return
    if "--fleet-util" in argv:
        # Standalone fleet rank-seconds aggregation rows (no
        # accelerator needed): simworld synthesized dumps at 64 and
        # 256 ranks through the post-mortem fleet analysis
        # (docs/fleet.md).
        for row in _fleet_util_rows():
            emit(row)
        return
    if "--fusion" in argv:
        # Standalone jit-lane fusion rows (CPU loopback subprocesses;
        # any box): fused vs unfused host-lane train step,
        # overlap_efficiency + critical-path blocking phase
        # (docs/fusion.md).
        for row in _fusion_rows():
            emit(row)
        return
    if "--quick" in argv:
        require_tpu("bench.py --quick")
        enable_compile_cache()
        for row in _quick_rows(batch, seq, steps):
            emit(row)
        return
    if "--mixed" in argv:
        require_tpu("bench.py --mixed")
        enable_compile_cache()
        emit(run_mixed(_same_size_cfg("float32"), batch, seq, steps))
        return
    if "--sweep-point" in argv:
        require_tpu("bench.py --sweep-point")
        enable_compile_cache()
        name = argv[argv.index("--sweep-point") + 1]
        _run_sweep_point(name, batch, seq, steps, emit)
        return
    if "--sweep" in argv:
        # Pipeline (schedule, V, accum) bubble rows are host math —
        # emitted on every substrate, before the measured lane. The
        # ZeRO grid (zero on/off x bucket size — docs/zero.md) runs on
        # CPU loopback subprocesses, so it is substrate-independent too.
        for row in _bubble_rows():
            emit(row)
        for row in _zero_sweep_rows():
            emit(row)
        for row in _fusion_rows():
            emit(row)
        # The measured lane spawns one chip child per point, so this
        # parent stays off JAX: the platform is asked in a child.
        _require_tpu_probe("--sweep")
        _run_sweep(batch, seq, steps, emit)
        return

    # One process runs every train-step row (the loopback lanes below
    # spawn only CPU children), so it can ask jax for the device itself.
    require_tpu("bench.py (default run)")
    enable_compile_cache()

    for row in _ring_busbw_rows():
        emit(row)
    for row in _hier_busbw_rows():
        emit(row)
    for row in _events_overhead_rows():
        emit(row)
    for row in _control_plane_scaling_rows():
        emit(row)
    for row in _serving_rows():
        emit(row)

    plan = full_run_plan(batch, seq, steps)
    _check_plan_order(plan)
    for _name, thunk in plan:
        emit(thunk())


if __name__ == "__main__":
    main()
