#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the training main path still
runs on the TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip  (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host: only the
                                     # multi-chip path and its reference

This parent process never imports jax: a chip belongs to one process at
a time. It runs each phase as a child (``chip_smoke.py --phase NAME``),
one at a time, each child exiting — and releasing the chip — before the
next starts. A child that fails, times out or reports a platform other
than ``tpu`` fails the whole run with a non-zero exit; nothing is
retried and nothing falls back. Children print their findings (device,
compile seconds, step ms, peak bytes, losses) as JSON lines as they go.
On success the LAST line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

One chip, all at the flagship's full width (``_flagship_cfg``: d_model
2048, d_ff 13312, 16/4 heads x 128, vocab 32768, 14 layers):

- ``build``       ``make core`` — the eager lane's native runtime.
- ``kernels``     flash fwd+bwd at the flagship shape vs
                  ``blockwise_attention``; ``decode_attention`` at a
                  serving shape; ``tpu_custom_call`` in the lowering.
- ``spmd_train``  ``parallel.make_split_train_step`` + ``optax.adam``.
- ``eager_train`` the same model and batch through ``hvd.init()`` →
                  jitted fwd/bwd → ``hvd.grouped_allreduce`` on the
                  ``xla_ici`` device plane → jitted apply.

Four chips (``--chips 4``): (a) ``horovodrun --tpu-pod`` starts one
eager rank per chip, gradients averaged on the device plane; then (b)
one process drives all four chips under ``create_mesh(data=4)``; the
per-step losses of (a) and (b) must agree.

Nothing is cut: every lane runs all 14 layers at full width.

``--rehearse`` is the CPU rehearsal for tests and for editing this file
without a chip: tiny model, interpret-mode kernels, four virtual CPU
devices. It can never print ``"ok": true``.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

# The flagship's shape: batch 4 x seq 2048, uncut. A compile-only
# memory_analysis() for a described v5e said the grad program would not
# fit (2.85 GB params + 2.85 GB grads + 6.15 GB temporaries beside 5.7
# GB of adam moments = 17.6 GB against 16 GiB); the chip run says it
# does (PR 21: 552 ms/step, PERF.md "Cells"). Only a chip run says.
BATCH, SEQ, STEPS = 4, 2048, 6

# Four-chip lane: global batch 8 x 2048, two rows per rank, same model,
# uncut. (The device plane's first multi-rank allreduce program staged
# every gradient as a flattened [1, k] row and concatenated them — ~5x
# its payload, 14.4 GB at 14 layers by the compiler's account, and the
# [1, k] reshape alone did not finish compiling on the chip. It now
# reduces every tensor in place in its own shape, jax/xla_ici.py.)
POD_BATCH, POD_STEPS = 8, 4

# Whole-script budget (the driver allows 1200 s) and per-phase caps.
DEADLINE_S = 1150
PHASE_CAP_S = {"build": 150, "kernels": 300, "spmd_train": 450,
               "eager_train": 450, "pod_eager": 360, "mesh_spmd": 300}

# Normalized max-abs error bounds, bf16 operands (8 mantissa bits; the
# kernel feeds bf16 probabilities to the MXU where the reference keeps
# f32): forward, backward.
KERNEL_TOL = {"fwd": 2e-2, "bwd": 5e-2}
# spmd vs eager run the SAME grad program on the same batch.
FIRST_LOSS_TOL = 1e-2
# (a) averages bf16 gradients across ranks on the device plane, (b) lets
# GSPMD reduce them: same math, different reduction order.
POD_LOSS_RTOL = 1e-2


def emit(obj):
    print(json.dumps(obj), flush=True)


# --------------------------------------------------- the model and its step


def _flagship_cfg():
    """The 1.43B pure-bf16 decoder this smoke trains: the widest
    Llama-family geometry whose params, grads and adam moments fit one
    16 GB chip beside 4 x 2048 tokens. head_dim 128 feeds the MXU
    full-depth contractions in the flash kernel, fewer-but-wider layers
    amortize the per-layer fixed costs, 4:1 GQA is the llama-3/mistral
    ratio. It is no published configuration (ROADMAP, named debts)."""
    from horovod_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=32768, d_model=2048, n_layers=14,
                       n_heads=16, n_kv_heads=4, d_ff=13312,
                       dtype="bfloat16", remat="attn+gate",
                       param_dtype="bfloat16")


def _step_jit_kwargs():
    """Compiler options of the train-step jits: the stock 16 MB
    scoped-VMEM budget under-buffers the big fused matmuls at these
    shapes, so 64 MB — what the benchmark's LM configurations set too
    (``chipbench/configs/*.json``)."""
    import jax

    if jax.devices()[0].platform != "tpu":
        return {}  # a TPU compiler option; the CPU rehearsal omits it
    return {"compiler_options": {"xla_tpu_scoped_vmem_limit_kib":
                                 "65536"}}


def _data(cfg, batch, seq):
    import jax
    import jax.numpy as jnp

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def make_eager_step(cfg):
    """The eager-Horovod step (hvd must already be initialized): jitted
    grad program, ``hvd.grouped_allreduce`` of the gradient tree over
    the device plane, jitted adam apply. Returns ``(step, (params,
    opt))`` with ``step(carry, data) -> (loss, carry)``."""
    import functools

    import jax
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.jax.optimizer import allreduce_gradients
    from horovod_tpu.models import llama_init, llama_loss

    # COMMITTED to the device from the start: the data plane's staging
    # device_put commits the gradients, so apply_fn outputs would flip
    # params from uncommitted to committed after step one — a new jit
    # signature, i.e. a silent mid-loop recompile of grad_fn.
    # This process's device: under a multi-rank launch jax.devices()[0]
    # is rank 0's chip.
    dev = jax.local_devices()[0]
    params = jax.device_put(llama_init(cfg, jax.random.PRNGKey(0)), dev)
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

    def step(carry, data):
        params, opt = carry
        loss, grads = grad_fn(params, data)
        # Donated: the fused device program reuses the gradients' HBM.
        grads = allreduce_gradients(grads, op=hvd.Average, donate=True)
        params, opt = apply_fn(grads, params, opt)
        return loss, (params, opt)

    return step, (params, opt)


# --------------------------------------------------------------- children


class _Child:
    """What every jax-touching child does first: place the compile
    cache, find the device, refuse anything that is not a TPU."""

    def __init__(self, phase, rehearse, before_backend=None):
        sys.path.insert(0, REPO)
        from horovod_tpu.utils.compile_cache import enable_compile_cache

        self.phase, self.rehearse = phase, rehearse
        self.cache_dir = enable_compile_cache()
        import jax

        self.cache_events = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        if before_backend is not None:
            # A rank of a multi-process job: jax.distributed (inside
            # hvd.init) must come up before anything touches the backend.
            before_backend(self)
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        if dev.platform != "tpu" and not rehearse:
            raise SystemExit(
                f"chip_smoke {phase}: needs a TPU, found platform "
                f"{dev.platform!r} ({dev.device_kind})")
        self.say(event="start", cache_dir=self.cache_dir)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_events["misses"] += 1

    def say(self, **fields):
        emit({"phase": self.phase, "device": self.device, **fields})

    def passed(self, **fields):
        self.say(passed=True, rehearsal=self.rehearse,
                 cache=dict(self.cache_events), **fields)


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel_err(got, ref):
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _median_ms(fn, reps=5):
    import jax

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return sorted(times)[len(times) // 2]


def phase_kernels(rehearse):
    c = _Child("kernels", rehearse)
    import importlib

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.parallel.ring_attention import blockwise_attention

    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    if rehearse:
        fa._INTERPRET = da._INTERPRET = True
        b, t, h, hkv, d = 2, 256, 4, 2, 64
        db, ds = 2, 128
    else:
        b, t, h, hkv, d = 4, 2048, 16, 4, 128   # the flagship's shape
        db, ds = 16, 640                        # a serving shape
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def ref(q, k, v):
        return blockwise_attention(q, k, v, causal=True)

    def grads_of(attn):
        # w rides as an ARGUMENT: closed over, its 64 MiB would be baked
        # into every program and into its compile-cache entry.
        return jax.jit(jax.grad(
            lambda q, k, v, w: jnp.sum(attn(q, k, v).astype(jnp.float32)
                                       * w),
            argnums=(0, 1, 2)))

    fwd, bwd = jax.jit(flash), grads_of(flash)
    for name, lowered in (("fwd", fwd.lower(q, k, v)),
                          ("fwd+bwd", bwd.lower(q, k, v, w))):
        if not rehearse and "tpu_custom_call" not in lowered.as_text():
            raise SystemExit(f"kernels: flash {name} lowered without a "
                             "tpu_custom_call — the reference path ran")
    err = {"fwd": _rel_err(fwd(q, k, v), jax.jit(ref)(q, k, v))}
    for name, g, g_ref in zip(("dq", "dk", "dv"), bwd(q, k, v, w),
                              grads_of(ref)(q, k, v, w)):
        err[name] = _rel_err(g, g_ref)
    c.say(event="flash_vs_blockwise", shape=[b, t, h, hkv, d], err=err,
          tol=KERNEL_TOL,
          fwd_ms=_median_ms(lambda: fwd(q, k, v)),
          fwd_bwd_ms=_median_ms(lambda: bwd(q, k, v, w)))
    bad = {n: e for n, e in err.items()
           if not e <= KERNEL_TOL["fwd" if n == "fwd" else "bwd"]}
    if bad:
        raise SystemExit(f"kernels: flash disagrees with blockwise: {bad}")

    dq = jax.random.normal(ks[4], (db, 1, h, d), jnp.bfloat16)
    ck = jax.random.normal(ks[5], (db, hkv, ds, d), jnp.bfloat16)
    cv = jax.random.normal(ks[6], (db, hkv, ds, d), jnp.bfloat16)
    pos = ds - 40
    fits = da.kernel_fits_vmem(dq.shape, ck.shape, ck.dtype)
    dec = jax.jit(da.decode_attention)
    lowered = "tpu_custom_call" in dec.lower(dq, ck, cv, pos).as_text()
    if lowered != fits and not rehearse:
        raise SystemExit(f"kernels: decode_attention lowered={lowered} "
                         f"but its VMEM gate said fits={fits}")
    derr = _rel_err(dec(dq, ck, cv, pos),
                    jax.jit(da._decode_attention_xla)(dq, ck, cv, pos))
    c.say(event="decode_vs_einsum", shape=[db, h, hkv, ds, d], err=derr,
          vmem_gate="kernel" if fits else "einsum (over the VMEM budget)",
          decode_ms=_median_ms(lambda: dec(dq, ck, cv, pos)))
    if not derr <= KERNEL_TOL["fwd"]:
        raise SystemExit(f"kernels: decode_attention err {derr}")
    c.passed(peak_bytes=_peak_bytes(jax.devices()[0]))


def _time_grad_compile(c, run):
    """Seconds to lower+compile the split step's grad program, built by
    the SAME expression ``make_split_train_step`` uses — so this compile
    lands in (or comes from) the persistent cache entry the real step
    then reads. Run by both train children: the first pays it cold, the
    second shows the cache hit across processes."""
    import jax

    grad = jax.jit(lambda p, d: jax.value_and_grad(run.loss_fn)(p, d),
                   **_step_jit_kwargs())
    t0 = time.perf_counter()
    lowered = grad.lower(run.params_abs, run.data)
    if not c.rehearse and "tpu_custom_call" not in lowered.as_text():
        raise SystemExit("train step lowered without the flash kernel")
    mem = lowered.compile().memory_analysis()
    seconds = time.perf_counter() - t0
    c.say(event="grad_compile", seconds=seconds,
          cache=dict(c.cache_events),
          memory_analysis={"args": mem.argument_size_in_bytes,
                           "out": mem.output_size_in_bytes,
                           "temp": mem.temp_size_in_bytes,
                           "peak": mem.peak_memory_in_bytes})
    return seconds


def _run_steps(c, step, carry, data, steps, reduce_loss=None):
    """``steps`` steps on one fixed batch; returns (losses, final carry,
    median ms of the steps after the first). Loss must be finite every
    step and lower at the end."""
    import math

    import jax

    losses, ms = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        loss, carry = step(carry, data)
        jax.block_until_ready((loss, carry))
        ms.append((time.perf_counter() - t0) * 1e3)
        loss = float(reduce_loss(loss, i) if reduce_loss else loss)
        losses.append(loss)
        c.say(event="step", i=i, loss=loss, ms=ms[-1])
        if not math.isfinite(loss):
            raise SystemExit(f"{c.phase}: loss {loss} at step {i}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"{c.phase}: loss did not fall: {losses}")
    return losses, carry, sorted(ms[1:])[len(ms[1:]) // 2]


def _train_setup(c, batch=BATCH):
    """The model, the fixed seeded batch and the sizes every train child
    works from: the flagship at full width (a toy in rehearsal)."""
    import types

    import jax

    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

    cfg, seq = (LlamaConfig.tiny(dtype="float32"), 64) if c.rehearse \
        else (_flagship_cfg(), SEQ)
    params_abs = jax.eval_shape(lambda k: llama_init(cfg, k),
                                jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params_abs)
    c.say(event="config", n_params=sum(x.size for x in leaves),
          n_layers=cfg.n_layers, d_model=cfg.d_model, d_ff=cfg.d_ff,
          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
          vocab=cfg.vocab_size, batch=batch, seq=seq)
    return types.SimpleNamespace(
        cfg=cfg, data=_data(cfg, batch, seq), params_abs=params_abs,
        loss_fn=lambda p, d: llama_loss(p, d, cfg),
        grad_bytes=sum(x.size * x.dtype.itemsize for x in leaves))


def phase_spmd_train(rehearse):
    c = _Child("spmd_train", rehearse)
    import jax
    import optax

    from horovod_tpu.models import llama_init
    from horovod_tpu.parallel import make_split_train_step

    run = _train_setup(c)
    compile_s = _time_grad_compile(c, run)
    ts = make_split_train_step(run.loss_fn, optax.adam(3e-4),
                               jit_kwargs=_step_jit_kwargs())
    carry = ts.init(llama_init(run.cfg, jax.random.PRNGKey(0)))
    losses, _, step_ms = _run_steps(c, ts.step, carry, run.data, STEPS)
    c.passed(losses=losses, step_ms=step_ms, grad_compile_s=compile_s,
             peak_bytes=_peak_bytes(jax.devices()[0]))


def _plane_bytes(snap):
    """(device-plane payload, host-ring payload + wire) bytes so far."""
    device = sum(v["bytes"] for v in snap["device_ops"].values())
    host = sum(v["bytes"] for v in snap["ops"].values())
    return device, host + snap["wire"]["tx_bytes"] + snap["wire"]["rx_bytes"]


def _hvd_init_on_plane(c):
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import xla_ici

    hvd.init()   # on a TPU: brings the device plane up, or raises
    if not xla_ici.active():
        raise SystemExit(f"{c.phase}: xla_ici device plane is not active")
    # Ring establishment books a few handshake bytes on the wire; what
    # the training steps move is counted from here.
    c.plane_baseline = _plane_bytes(hvd.metrics())


def _assert_device_plane(c, hvd, grad_bytes, steps):
    device, host = (now - base for now, base in zip(
        _plane_bytes(hvd.metrics()), c.plane_baseline))
    c.say(event="planes", device_plane_bytes=device, host_ring_bytes=host,
          grad_bytes_per_step=grad_bytes)
    if device < grad_bytes * steps or host != 0:
        raise SystemExit(
            f"{c.phase}: gradients must ride the device plane: booked "
            f"{device} B there (need >= {grad_bytes * steps}) and "
            f"{host} B on the host ring (need 0)")
    return device


def phase_eager_train(rehearse):
    c = _Child("eager_train", rehearse)
    import jax

    import horovod_tpu.jax as hvd

    run = _train_setup(c)
    compile_s = _time_grad_compile(c, run)
    _hvd_init_on_plane(c)
    try:
        step, carry = make_eager_step(run.cfg)
        losses, _, step_ms = _run_steps(c, step, carry, run.data, STEPS)
        device = _assert_device_plane(c, hvd, run.grad_bytes, STEPS)
    finally:
        hvd.shutdown()
    c.passed(losses=losses, step_ms=step_ms, grad_compile_s=compile_s,
             device_plane_bytes=device,
             peak_bytes=_peak_bytes(jax.devices()[0]))


def phase_pod_rank(rehearse):
    """One rank of (a), started by ``horovodrun --tpu-pod``."""
    c = _Child("pod_rank", rehearse, before_backend=_hvd_init_on_plane)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu.jax as hvd

    try:
        rank, size = hvd.rank(), hvd.size()
        c.phase = f"pod_rank{rank}"
        if size != 4 or len(jax.devices()) != 4:
            raise SystemExit(f"pod_rank: want 4 ranks on 4 chips, have "
                             f"size {size}, {len(jax.devices())} devices")
        run = _train_setup(c, POD_BATCH)
        rows = POD_BATCH // size
        local = jax.tree.map(lambda x: x[rank * rows:(rank + 1) * rows],
                             run.data)
        step, carry = make_eager_step(run.cfg)

        def global_loss(loss, i):  # equal shards: mean of rank means
            return hvd.allreduce(loss, name=f"loss.{i}", op=hvd.Average)

        losses, (params, _), step_ms = _run_steps(
            c, step, carry, local, POD_STEPS, reduce_loss=global_loss)
        checksum = sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree.leaves(params))
        sums = np.asarray(hvd.allgather(jnp.reshape(checksum, (1,)),
                                        name="checksum"))
        if not (sums == sums[0]).all():
            raise SystemExit(f"pod_rank{rank}: parameter checksums "
                             f"differ across ranks: {sums.tolist()}")
        device = _assert_device_plane(c, hvd, run.grad_bytes, POD_STEPS)
        local_device = str(jax.local_devices()[0])
    finally:
        hvd.shutdown()
    c.passed(rank=rank, losses=losses, step_ms=step_ms,
             chip=local_device, checksum=float(sums[0]),
             device_plane_bytes=device,
             peak_bytes=_peak_bytes(jax.local_devices()[0]))


def phase_mesh_spmd(rehearse):
    """(b): one process, four chips, ``create_mesh(data=4)``."""
    c = _Child("mesh_spmd", rehearse)
    import jax
    import optax

    from horovod_tpu import parallel
    from horovod_tpu.models import (
        llama_init,
        llama_loss,
        llama_partition_rules,
    )
    from horovod_tpu.parallel.sharding import apply_sharding

    if len(jax.devices()) != 4:
        raise SystemExit(f"mesh_spmd: want 4 devices, have "
                         f"{len(jax.devices())}")
    run = _train_setup(c, POD_BATCH)
    cfg = run.cfg
    mesh = parallel.create_mesh(data=4)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    params = apply_sharding(params, parallel.shard_params(
        params, mesh, llama_partition_rules()))
    data = jax.device_put(
        run.data, parallel.named_sharding(mesh, ("data", "fsdp"), "seq"))
    ts = parallel.make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg, mesh), optax.adam(3e-4),
        jit_kwargs=_step_jit_kwargs())
    losses, (params, _), step_ms = _run_steps(
        c, ts.step, ts.init(params), data, POD_STEPS)
    shard_devices = sorted(str(s.device) for s in
                           data["tokens"].addressable_shards)
    param_devices = {len(x.sharding.device_set)
                     for x in jax.tree.leaves(params)}
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    c.say(event="placement", batch_shard_devices=shard_devices,
          param_device_counts=sorted(param_devices), bytes_in_use=in_use)
    if len(set(shard_devices)) != 4 or param_devices != {4} \
            or any(b == 0 for b in in_use):
        raise SystemExit("mesh_spmd: work is not spread over four "
                         "distinct devices")
    c.passed(losses=losses, step_ms=step_ms,
             peak_bytes=[_peak_bytes(d) for d in jax.devices()])


CHILD_PHASES = {"kernels": phase_kernels, "spmd_train": phase_spmd_train,
                "eager_train": phase_eager_train,
                "pod_rank": phase_pod_rank, "mesh_spmd": phase_mesh_spmd}


# ----------------------------------------------------------------- parent


class PhaseFailed(Exception):
    pass


def _run(name, cmd, deadline, env=None):
    """Run one phase's process group to its end; stream its stdout
    through; return the JSON objects it printed. Raises PhaseFailed on a
    non-zero exit or a timeout (the whole group is killed)."""
    budget = min(PHASE_CAP_S[name], deadline - time.monotonic())
    if budget <= 0:
        raise PhaseFailed(f"{name}: no time left in the {DEADLINE_S} s "
                          "budget")
    emit({"phase": name, "event": "spawn", "cmd": cmd,
          "timeout_s": round(budget)})
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    timed_out = threading.Event()

    def kill():
        timed_out.set()
        os.killpg(proc.pid, signal.SIGKILL)

    timer = threading.Timer(budget, kill)
    timer.start()
    rows = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            start = line.find("{")
            if start >= 0:
                try:
                    rows.append(json.loads(line[start:]))
                except json.JSONDecodeError:
                    pass
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # stragglers of the group
        except ProcessLookupError:
            pass
    if timed_out.is_set():
        raise PhaseFailed(f"{name}: killed after {budget:.0f} s")
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}")
    emit({"phase": name, "event": "done",
          "seconds": round(time.monotonic() - t0, 1)})
    return rows


def _passed_rows(name, rows, want, rehearse):
    """The ``passed`` result rows of a phase, each on a TPU."""
    got = [r for r in rows if r.get("passed") is True
           and isinstance(r.get("device"), dict)]
    if len(got) != want:
        raise PhaseFailed(f"{name}: {len(got)} result rows, want {want}")
    for r in got:
        if r["device"]["platform"] != "tpu" and not rehearse:
            raise PhaseFailed(f"{name}: ran on {r['device']}")
    return got


def _child_cmd(phase, rehearse):
    return [sys.executable, os.path.abspath(__file__), "--phase", phase] \
        + (["--rehearse"] if rehearse else [])


def _close(a, b, tol, what):
    if not abs(a - b) <= tol:
        raise PhaseFailed(f"{what}: {a} vs {b} (tolerance {tol})")


def run_one_chip(deadline, rehearse, env):
    _run("build", ["make", "-s", "core"], deadline)
    results = {}
    for phase in ("kernels", "spmd_train", "eager_train"):
        rows = _run(phase, _child_cmd(phase, rehearse), deadline, env)
        results[phase] = _passed_rows(phase, rows, 1, rehearse)[0]
    spmd, eager = results["spmd_train"], results["eager_train"]
    _close(eager["losses"][0], spmd["losses"][0], FIRST_LOSS_TOL,
           "first-step loss, eager_train vs spmd_train")
    emit({"phase": "summary", "first_loss": {
              "spmd_train": spmd["losses"][0],
              "eager_train": eager["losses"][0]},
          "grad_compile_s": {
              "spmd_train": spmd["grad_compile_s"],
              "spmd_train_cache": spmd["cache"],
              "eager_train_same_program": eager["grad_compile_s"],
              "eager_train_cache": eager["cache"]},
          "step_ms": {p: results[p]["step_ms"]
                      for p in ("spmd_train", "eager_train")},
          "peak_bytes": {p: r["peak_bytes"] for p, r in results.items()}})
    return eager["device"]


def run_four_chips(deadline, rehearse, env):
    # Prerequisite, not a measured phase: the ranks load the native core.
    _run("build", ["make", "-s", "core"], deadline)
    launch = [sys.executable, "-m", "horovod_tpu.runner.launch"] \
        + (["-np", "4"] if rehearse else ["--tpu-pod"])
    rows = _run("pod_eager", launch + _child_cmd("pod_rank", rehearse),
                deadline, env)
    ranks = sorted(_passed_rows("pod_eager", rows, 4, rehearse),
                   key=lambda r: r["rank"])
    if [r["rank"] for r in ranks] != [0, 1, 2, 3]:
        raise PhaseFailed(f"pod_eager: ranks {[r['rank'] for r in ranks]}")
    if not rehearse and len({r["chip"] for r in ranks}) != 4:
        raise PhaseFailed(f"pod_eager: ranks share chips: "
                          f"{[r['chip'] for r in ranks]}")
    if len({r["checksum"] for r in ranks}) != 1:
        raise PhaseFailed("pod_eager: parameter checksums differ")
    if rehearse:
        env = dict(env,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
    rows = _run("mesh_spmd", _child_cmd("mesh_spmd", rehearse), deadline,
                env)
    mesh = _passed_rows("mesh_spmd", rows, 1, rehearse)[0]
    for i, (a, b) in enumerate(zip(ranks[0]["losses"], mesh["losses"])):
        _close(a, b, POD_LOSS_RTOL * abs(b),
               f"step {i} loss, pod_eager vs mesh_spmd")
    emit({"phase": "summary", "losses": {"pod_eager": ranks[0]["losses"],
                                         "mesh_spmd": mesh["losses"]},
          "step_ms": {"pod_eager": [r["step_ms"] for r in ranks],
                      "mesh_spmd": mesh["step_ms"]},
          "chips": [r["chip"] for r in ranks]})
    return mesh["device"]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help="child mode: run ONE phase in this process")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes (tests, editing "
                         "without a chip); never prints \"ok\": true")
    args = ap.parse_args()

    if args.phase:
        try:
            CHILD_PHASES[args.phase](args.rehearse)
        except BaseException as e:  # noqa: BLE001 — re-raised as an exit
            # Leave NOW, whatever threads the runtimes still hold: a
            # failed rank that lingers in teardown keeps its peers — and
            # their chips — waiting on it until the phase's time limit.
            if isinstance(e, SystemExit):
                print(e, file=sys.stderr)
            else:
                traceback.print_exc()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)
        return 0

    env = None
    if args.rehearse:
        # The device plane on the CPU backend; four virtual devices for
        # the mesh child only (each pod rank is one device of four).
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HOROVOD_XLA_DATA_PLANE="1")
    deadline = time.monotonic() + DEADLINE_S
    try:
        device = (run_four_chips if args.chips == 4 else run_one_chip)(
            deadline, args.rehearse, env)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    if device["count"] != args.chips and not args.rehearse:
        print(f"chip_smoke: FAILED — --chips {args.chips} but jax "
              f"reports {device['count']} devices", file=sys.stderr)
        return 1
    if args.rehearse:
        emit({"ok": False, "rehearsal": "passed", "device": device})
        return 0
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
