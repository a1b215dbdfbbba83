"""Flagship transformer: correctness + sharded train-step compilation on
the 8-virtual-device mesh (the shape of the driver's dryrun_multichip)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import parallel
from horovod_tpu.models import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
    llama_partition_rules,
)
from horovod_tpu.parallel.sharding import apply_sharding, named_sharding


# One compiled program a configuration: evaluated eagerly the model is
# a compile a primitive (7 to 10 s a call), and
# ``test_forward_shapes_and_determinism`` is the file's case that makes
# the EAGER call, which users make too.
_forward = jax.jit(llama_forward, static_argnums=2,
                   static_argnames="return_aux")
_grads = jax.jit(jax.grad(llama_loss), static_argnums=2)


def _loss_and_grads(cfg, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg)))(params)


def _assert_same_loss_and_grads(got, ref, atol=lambda b: 1e-6):
    """The file's bounds for "a pure scheduling choice": the loss to
    1e-6, every gradient leaf to rtol 1e-5 and ``atol`` of the leaf."""
    np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=atol(b)),
        got[1], ref[1])


def test_forward_shapes_and_determinism():
    cfg = LlamaConfig.tiny(dtype="float32")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = llama_forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(llama_forward(params, tokens, cfg)), np.asarray(logits))


def test_causality():
    # Changing a future token must not change past logits.
    cfg = LlamaConfig.tiny(dtype="float32")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 7].set(5)
    l1 = _forward(params, t1, cfg)
    l2 = _forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :7]), np.asarray(l2[0, :7]),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(l1[0, 7]), np.asarray(l2[0, 7]))


def test_sharded_train_step_matches_single_device():
    """dp=2 x fsdp=2 x tensor=2 (+ring attention via seq in the next test):
    the sharded train step must produce the same loss and params as the
    unsharded one."""
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    # SGD: parameter deltas are linear in the gradient, so they compare
    # cleanly across shardings (adam's eps-normalized first step would
    # amplify 1e-8 reduction-order noise on near-zero grads to full
    # lr-sized sign flips).
    tx = optax.sgd(1e-1)
    opt = tx.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def step(params, opt, batch, mesh=None):
        loss, grads = jax.value_and_grad(llama_loss)(params, batch, cfg,
                                                     mesh)
        updates, opt = tx.update(grads, opt, params)
        return loss, optax.apply_updates(params, updates), opt

    loss_ref, params_ref, _ = jax.jit(
        lambda p, o, b: step(p, o, b))(params, opt, batch)

    mesh = parallel.create_mesh(data=2, fsdp=2, tensor=2)
    shardings = parallel.shard_params(params, mesh, llama_partition_rules())
    p_sh = apply_sharding(params, shardings)
    opt_sh = tx.init(p_sh)
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), None))

    sharded_step = jax.jit(lambda p, o, b: step(p, o, b, mesh))
    loss_sh, params_new, _ = sharded_step(p_sh, opt_sh, b_sh)

    np.testing.assert_allclose(float(loss_sh), float(loss_ref), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(params_ref),
                     jax.tree.leaves(params_new)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-6)


def _interpret_flash_kernels(monkeypatch):
    import importlib

    monkeypatch.setattr(
        importlib.import_module("horovod_tpu.ops.flash_attention"),
        "_INTERPRET", True)


@pytest.fixture(scope="module")
def flash_block_model():
    """-> (cfg, params, batch, the kernel-default loss and gradients on
    the interpret-mode kernels)."""
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2, remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    with pytest.MonkeyPatch.context() as monkeypatch:
        _interpret_flash_kernels(monkeypatch)
        return cfg, params, batch, _loss_and_grads(cfg, params, batch)


@pytest.mark.parametrize("block", [16, 512])  # below t=32 / clamped to it
def test_flash_block_is_pure_scheduling(block, flash_block_model,
                                        monkeypatch):
    """LlamaConfig.flash_block (the sweep knob for the pallas
    q/k grid blocks) must not change the math: loss and grads match the
    kernel-default config. Runs the REAL pallas kernels in interpret
    mode (the XLA fallback ignores the block args, which would make
    this test vacuous on CPU) — an oversized block exercises
    _pick_block's clamp-to-sequence too."""
    cfg, params, batch, ref = flash_block_model
    _interpret_flash_kernels(monkeypatch)
    _assert_same_loss_and_grads(
        _loss_and_grads(dataclasses.replace(cfg, flash_block=block), params,
                        batch), ref)


def test_seq_parallel_forward_matches():
    """Ring-attention path (seq=4) must match the single-device forward."""
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                                cfg.vocab_size)
    ref = _forward(params, tokens, cfg)

    mesh = parallel.create_mesh(data=2, seq=4)
    shardings = parallel.shard_params(params, mesh, llama_partition_rules())
    p_sh = apply_sharding(params, shardings)
    t_sh = jax.device_put(tokens,
                          named_sharding(mesh, ("data", "fsdp"), "seq"))
    out = jax.jit(
        lambda p, t: llama_forward(p, t, cfg, mesh))(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_seq_parallel_ulysses_matches():
    """Ulysses path (seq_parallel="ulysses", seq=4) must match the
    single-device forward (tiny config has 4 heads -> divisible)."""
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2,
                           seq_parallel="ulysses")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                                cfg.vocab_size)
    ref = _forward(params, tokens, cfg)

    mesh = parallel.create_mesh(data=2, seq=4)
    shardings = parallel.shard_params(params, mesh, llama_partition_rules())
    p_sh = apply_sharding(params, shardings)
    t_sh = jax.device_put(tokens,
                          named_sharding(mesh, ("data", "fsdp"), "seq"))
    out = jax.jit(
        lambda p, t: llama_forward(p, t, cfg, mesh))(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)

# ---- sparse mixture-of-experts (expert parallelism) ----

def test_moe_forward_and_aux():
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["moe_gate"].shape == (
        cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits, aux = _forward(params, tokens, cfg, return_aux=True)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # All K choices counted: K at perfectly uniform routing, E at
    # total collapse (moe_balance_loss).
    assert 0.9 * cfg.n_experts_per_token < float(aux) \
        < float(cfg.n_experts)


def test_moe_routing_is_sparse():
    # Zeroing an expert's weights must change ONLY tokens routed to it;
    # with k=1 routing, tokens routed elsewhere are bit-identical.
    cfg = LlamaConfig.tiny_moe(dtype="float32", n_layers=1, remat=False,
                               n_experts_per_token=1, capacity_factor=4.0)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    # Enough tokens that every expert gets traffic with overwhelming
    # probability (routing is data-dependent).
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    ref = np.asarray(_forward(params, tokens, cfg))
    mutated = jax.tree.map(lambda x: x, params)
    mutated["layers"]["moe_down"] = (
        params["layers"]["moe_down"].at[:, 0].set(0.0))
    out = np.asarray(_forward(mutated, tokens, cfg))
    changed = ~np.isclose(ref, out).all(axis=-1)  # [B, T] per-token
    assert changed.any(), "no token used expert 0"
    assert not changed.all(), "zeroing one expert changed every token"


def test_moe_train_step_decreases_loss():
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(llama_loss)(p, batch, cfg)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    losses = []
    for _ in range(6):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_moe_expert_parallel_matches_single_device():
    """EP×TP×FSDP sharded MoE step must produce the same loss as the
    unsharded one (same init, same batch)."""
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")
    # Pin the GShard dispatch on BOTH sides: this test certifies EP
    # sharding, and the mesh-free default would otherwise pick the
    # dropless grouped path whose no-drop semantics legitimately
    # diverge from capacity-1.25 GShard (see
    # test_grouped_moe_matches_gshard_when_dropless for that parity).
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False,
                               moe_impl="gshard")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    ref = float(llama_loss(params, batch, cfg))

    mesh = parallel.create_mesh(fsdp=2, expert=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh, llama_partition_rules()))
    b_sh = jax.device_put(batch, named_sharding(mesh, ("data", "fsdp"),
                                                "seq"))
    loss = jax.jit(lambda p, b: llama_loss(p, b, cfg, mesh))(p_sh, b_sh)
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)


# ---- pipeline parallelism (GPipe over the "pipe" axis) ----

def _skip_unless_8():
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")


def test_pipeline_forward_matches_single_device():
    _skip_unless_8()
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    ref = np.asarray(_forward(params, tokens, cfg))

    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    t_sh = jax.device_put(tokens,
                          named_sharding(mesh, ("data", "fsdp"), "seq"))
    out = jax.jit(lambda p, t: llama_forward(p, t, cfg, mesh))(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_pipeline_train_step_matches_single_device():
    """Loss AND updated params must match the unsharded step — the param
    comparison is what exercises the gpipe backward pass (grads through
    ppermute + masked collection). SGD so deltas are linear in the
    gradient (see test_sharded_train_step_matches_single_device)."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    tx = optax.sgd(1e-1)

    def step(p, o, bt, mesh=None):
        loss, g = jax.value_and_grad(llama_loss)(p, bt, cfg, mesh)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    p_ref, _, ref_loss = jax.jit(lambda p, o, b: step(p, o, b))(
        params, tx.init(params), batch)

    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(batch, named_sharding(mesh, ("data", "fsdp"),
                                                "seq"))
    p2, o2, loss = jax.jit(lambda p, o, b: step(p, o, b, mesh))(
        p_sh, tx.init(p_sh), b_sh)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-6)


def test_pipeline_with_moe():
    """PP x EP x TP: logits must match; the loss differs only by the
    per-microbatch aux term (Switch aux is nonlinear in batch)."""
    _skip_unless_8()
    # gshard pinned on both sides: mesh-free "auto" would pick the
    # dropless grouped path, which legitimately diverges from
    # capacity-1.25 GShard on overflow tokens.
    cfg = LlamaConfig.tiny_moe(dtype="float32", n_layers=4, remat=False,
                               moe_impl="gshard")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    ref = np.asarray(_forward(params, tokens, cfg))

    mesh = parallel.create_mesh(pipe=2, expert=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    t_sh = jax.device_put(tokens,
                          named_sharding(mesh, ("data", "fsdp"), "seq"))
    out = jax.jit(lambda p, t: llama_forward(p, t, cfg, mesh))(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_pipeline_rejects_seq_parallel():
    _skip_unless_8()
    import pytest
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 16), jnp.int32)
    mesh = parallel.create_mesh(pipe=2, seq=2, tensor=2,
                                devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="sequence parallelism"):
        llama_forward(params, tokens, cfg, mesh)


def test_pipeline_bf16_compiles_on_cpu():
    """bf16 activations through the pipeline must not hit XLA CPU's
    AllReducePromotion crash (regression: gpipe runs f32 on CPU)."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(n_layers=4, remat=False)  # default bf16
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(batch, named_sharding(mesh, ("data", "fsdp"),
                                                "seq"))
    loss, grads = jax.jit(
        jax.value_and_grad(lambda p, b: llama_loss(p, b, cfg, mesh)))(
            p_sh, b_sh)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g, dtype=np.float32)).all()
               for g in jax.tree.leaves(grads))


def test_param_dtype_bf16():
    """param_dtype="bfloat16" stores every leaf in bf16 (the pure-bf16
    large-model recipe) and the forward/loss stays finite."""
    cfg = LlamaConfig.tiny(dtype="bfloat16", param_dtype="bfloat16",
                           n_layers=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(params))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    out = _forward(params, tokens, cfg)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    loss = llama_loss(params, {"tokens": tokens,
                               "targets": jnp.roll(tokens, -1, 1)}, cfg)
    assert bool(jnp.isfinite(loss))


def test_param_dtype_bf16_sharded():
    """bf16 params compose with TP+FSDP sharding (partition rules are
    dtype-agnostic); the sharded train step runs and stays finite."""
    cfg = LlamaConfig.tiny(dtype="bfloat16", param_dtype="bfloat16",
                           n_layers=2)
    mesh = parallel.create_mesh(data=2, fsdp=2, tensor=2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    shardings = parallel.shard_params(params, mesh, llama_partition_rules())
    p_sh = apply_sharding(params, shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                cfg.vocab_size)
    t_sh = jax.device_put(tokens,
                          named_sharding(mesh, ("data", "fsdp"), "seq"))
    tx = optax.adam(1e-3)
    opt = tx.init(p_sh)

    @jax.jit
    def step(p, o, t):
        loss, grads = jax.value_and_grad(llama_loss)(
            p, {"tokens": t, "targets": jnp.roll(t, -1, 1)}, cfg, mesh)
        updates, o = tx.update(grads, o, p)
        return loss, optax.apply_updates(p, updates), o

    loss, p2, opt = step(p_sh, opt, t_sh)
    assert bool(jnp.isfinite(loss))
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(p2))


def test_master_weights_tracks_fp32_training():
    """bf16-compute + fp32-master training must track full-fp32 training
    closely (and the master/moments must actually be fp32) — the loss
    parity contract for the mixed-precision recipe."""
    import functools

    import optax

    from horovod_tpu.parallel import master_weights

    cfg32 = LlamaConfig.tiny(d_model=64, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=128, vocab_size=128,
                             dtype="float32", remat=False)
    cfgmw = dataclasses.replace(cfg32, dtype="bfloat16")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg32.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def run(cfg, use_master, steps=8):
        params = llama_init(cfg, jax.random.PRNGKey(0))
        tx = optax.adam(1e-2)
        losses = []
        if use_master:
            mw = master_weights(tx)
            state = mw.init(params)
            assert all(x.dtype == jnp.float32
                       for x in jax.tree.leaves(state.master))
            assert all(x.dtype == jnp.float32
                       for x in jax.tree.leaves(state.inner)
                       if x.dtype in (jnp.float32, jnp.bfloat16))

            @jax.jit
            def step(state, batch):
                p = mw.compute_params(state)
                loss, grads = jax.value_and_grad(llama_loss)(p, batch,
                                                             cfg)
                return loss, mw.apply(state, grads)

            for _ in range(steps):
                loss, state = step(state, batch)
                losses.append(float(loss))
        else:
            opt = tx.init(params)

            @jax.jit
            def step(params, opt, batch):
                loss, grads = jax.value_and_grad(llama_loss)(params,
                                                             batch, cfg)
                updates, opt = tx.update(grads, opt, params)
                return loss, optax.apply_updates(params, updates), opt

            for _ in range(steps):
                loss, params, opt = step(params, opt, batch)
                losses.append(float(loss))
        return losses

    ref = run(cfg32, use_master=False)
    mixed = run(cfgmw, use_master=True)
    # both optimize; final losses agree to bf16-forward tolerance
    assert ref[-1] < ref[0] and mixed[-1] < mixed[0]
    assert abs(ref[-1] - mixed[-1]) / abs(ref[-1]) < 0.05, (ref, mixed)


# ---- split-program train step + fused optimizer apply (round 6) ----

def _tiny_train_setup(batch_shape=(4, 16)):
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2, remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), batch_shape, 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    return cfg, params, batch


def _monolithic_step(cfg, tx, params, batch):
    @jax.jit
    def step(p, o, b):
        loss, grads = jax.value_and_grad(llama_loss)(p, b, cfg)
        updates, o = tx.update(grads, o, p)
        return loss, optax.apply_updates(p, updates)

    return step(params, tx.init(params), batch)


def test_split_step_matches_monolithic():
    """The two-program step (grad jit + apply jit, donated buffers)
    must reproduce the single monolithic jit exactly: same loss, same
    updated params. SGD so parameter deltas are linear in the gradient
    (see test_sharded_train_step_matches_single_device)."""
    from horovod_tpu.parallel import make_split_train_step

    cfg, params, batch = _tiny_train_setup()
    tx = optax.sgd(1e-1)
    ref_loss, ref_params = _monolithic_step(cfg, tx, params, batch)

    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), tx)
    loss, (p2, _) = ts.step(ts.init(params), batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        ref_params, p2)


def test_split_step_2way_accumulation_matches_monolithic():
    """2-way microbatch gradient accumulation (two sequential calls to
    the grad program into a donated accumulator, 1/N loss scaling
    inside the program) must equal the full-batch monolithic step to
    f32 reduction-order tolerance — the pin that certifies the r6
    MoE/flagship attack formulation computes the same math."""
    from horovod_tpu.parallel import make_split_train_step

    cfg, params, batch = _tiny_train_setup()
    tx = optax.sgd(1e-1)
    ref_loss, ref_params = _monolithic_step(cfg, tx, params, batch)

    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), tx, microbatches=2)
    loss, (p2, _) = ts.step(ts.init(params), batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        ref_params, p2)


def test_split_step_rejects_indivisible_microbatches():
    import pytest

    from horovod_tpu.parallel import make_split_train_step

    cfg, params, batch = _tiny_train_setup(batch_shape=(4, 16))
    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), optax.sgd(1e-1),
        microbatches=3)
    with pytest.raises(ValueError, match="microbatches"):
        ts.step(ts.init(params), batch)


def test_fused_adam_matches_optax():
    """The single-pass fused adam (parallel.fused_adam) is the same
    optimizer as optax.adam — moments, bias correction, update — just
    expressed as one fused elementwise pass per leaf. Multi-step so the
    count/bias-correction trajectory is covered."""
    from horovod_tpu.parallel import fused_adam

    cfg, params, batch = _tiny_train_setup()
    grads = _grads(params, batch, cfg)

    tx = optax.adam(1e-2)
    opt = tx.init(params)
    p_ref = params
    fa = fused_adam(1e-2)
    st = fa.init(params)
    p_f = params
    for _ in range(3):
        updates, opt = tx.update(grads, opt, p_ref)
        p_ref = optax.apply_updates(p_ref, updates)
        p_f, st = fa.apply(p_f, grads, st)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        p_ref, p_f)
    assert int(st.count) == 3


def test_fused_master_adam_matches_split_master():
    """fused_master_adam (adam + master cast in ONE pass) must track
    the split formulation (master_weights(optax.adam) then
    compute_params) exactly: same fp32 master trajectory, same bf16
    compute cast; moments stay fp32."""
    from horovod_tpu.parallel import fused_master_adam, master_weights

    cfg, params, batch = _tiny_train_setup()
    grads = _grads(params, batch, cfg)

    mw = master_weights(optax.adam(1e-2))
    mw_state = mw.init(params)
    fm = fused_master_adam(1e-2)
    fm_state = fm.init(params)
    compute = fm.compute_params(fm_state)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(compute))
    assert all(x.dtype == jnp.float32
               for t in (fm_state.master, fm_state.mu, fm_state.nu)
               for x in jax.tree.leaves(t))
    for _ in range(3):
        mw_state = mw.apply(mw_state, grads)
        compute, fm_state = fm.apply(compute, grads, fm_state)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        mw_state.master, fm_state.master)
    # The fused cast IS the fused master rounded to bf16, bitwise.
    jax.tree.map(
        lambda m, c: np.testing.assert_array_equal(
            np.asarray(m.astype(jnp.bfloat16), dtype=np.float32),
            np.asarray(c, dtype=np.float32)),
        fm_state.master, compute)
    # Across the two formulations the casts agree to bf16 resolution
    # (masters within 1e-6 can round across a bf16 ULP boundary).
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32), rtol=1e-2, atol=1e-3),
        mw.compute_params(mw_state), compute)


def test_split_step_with_fused_master_trains():
    """End-to-end: split-program step + 2-way accumulation + the fused
    master-adam apply optimizes (the carry holds the bf16 compute cast;
    the fp32 master lives in the optimizer state)."""
    from horovod_tpu.parallel import (
        fused_master_adam,
        make_split_train_step,
    )

    cfg, params, batch = _tiny_train_setup()
    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), fused_master_adam(1e-2),
        microbatches=2)
    carry = ts.init(params)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(carry[0]))
    losses = []
    for _ in range(6):
        loss, carry = ts.step(carry, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("optimizer", ["fused_master_adam", "optax.adam"])
def test_apply_jit_emits_no_donation_warning(optimizer, hvdlint):
    """The split step's apply jit must donate ONLY buffers XLA can
    actually alias (params + optimizer state; gradients have no
    matching output). The fp32-master path used to warn "Some donated
    buffers were not usable" on every compute-cast leaf (BENCH r5
    tail); this pins the r6 argument-layout fix for BOTH the fused
    master-adam apply and the optax split apply, on bf16-param
    configs where grads/params/master dtypes actually differ — at
    runtime (the XLA warning) AND statically (hvdlint's C4 check over
    the same step program, the pre-commit form of this class)."""
    import warnings

    from horovod_tpu.parallel import (
        fused_master_adam,
        make_split_train_step,
    )

    cfg = LlamaConfig.tiny(n_layers=2, remat=False,
                           param_dtype="bfloat16")  # bf16 compute+store
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    tx = {"fused_master_adam": fused_master_adam,
          "optax.adam": optax.adam}[optimizer](1e-2)
    ts = make_split_train_step(
        lambda p, d: llama_loss(p, d, cfg), tx, microbatches=2)
    carry0 = jax.eval_shape(ts.init, params)
    hvdlint(ts.step, (carry0, batch))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loss, carry = ts.step(ts.init(params), batch)
        jax.block_until_ready(loss)
    bad = [w for w in caught
           if "donated buffers were not usable" in str(w.message)]
    assert not bad, [str(w.message) for w in bad]


def _remat_free(cfg0):
    """-> (cfg0, params, batch, loss and gradients under no remat)."""
    params = llama_init(cfg0, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg0.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    return cfg0, params, batch, _loss_and_grads(cfg0, params, batch)


@pytest.fixture(scope="module")
def dense_without_remat():
    return _remat_free(LlamaConfig.tiny(dtype="float32", n_layers=2,
                                        remat=False))


@pytest.fixture(scope="module")
def moe_without_remat():
    return _remat_free(LlamaConfig.tiny_moe(dtype="float32", n_layers=2,
                                            remat=False))


@pytest.mark.parametrize("mode", ["attn", "attn+gate", "attn+gate+qkv",
                                  "attn+ffn", "dots", "full"])
def test_remat_modes_agree_on_gradients(mode, dense_without_remat):
    """Every remat policy is a pure scheduling choice: loss and grads
    must match remat=False bit-for-bit-ish (f32 tolerances). Covers the
    r4 'attn+gate'/'attn+ffn' modes whose saved FFN residuals must not
    change the math."""
    cfg0, params, batch, ref = dense_without_remat
    _assert_same_loss_and_grads(_loss_and_grads(
        dataclasses.replace(cfg0, remat=mode), params, batch), ref)


# attn+moe / moe cover the grouped path's saved residuals (the sorted
# order, its inverse and the gate weights in that order; pre-silu gate
# and up) — remat must stay scheduling-only.
@pytest.mark.parametrize("mode", ["attn", "attn+gate", "attn+gate+qkv",
                                  "attn+ffn", "attn+moe", "moe", "dots",
                                  "full"])
def test_remat_modes_agree_on_gradients_moe(mode, moe_without_remat):
    """Same scheduling-only contract for the MoE layer — covers the
    saved moe_dispatch/moe_combine residuals under attn+gate."""
    cfg0, params, batch, ref = moe_without_remat
    _assert_same_loss_and_grads(_loss_and_grads(
        dataclasses.replace(cfg0, remat=mode), params, batch), ref)


def test_unknown_remat_mode_rejected():
    cfg = LlamaConfig.tiny(dtype="float32", remat="bogus")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    with pytest.raises(ValueError, match="unknown remat mode"):
        llama_forward(params, tokens, cfg)


def test_moe_remat_modes_rejected_without_grouped_dispatch():
    """attn+moe / moe save residuals only grouped_moe_ffn emits — a
    dense config or a forced-GShard one must fail loudly instead of
    silently degrading to plain attn remat."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 256)
    dense = LlamaConfig.tiny(dtype="float32", remat="attn+moe")
    with pytest.raises(ValueError, match="grouped MoE dispatch"):
        llama_forward(llama_init(dense, jax.random.PRNGKey(0)), tokens,
                      dense)
    gshard = LlamaConfig.tiny_moe(dtype="float32", remat="moe",
                                  moe_impl="gshard")
    with pytest.raises(ValueError, match="grouped MoE dispatch"):
        llama_forward(llama_init(gshard, jax.random.PRNGKey(0)), tokens,
                      gshard)


def _seam_model(**kw):
    """Two layers whose heads are a whole 128-lane slab, q and k normed
    a head: what ``ops/qk_prep.py``'s predicate takes."""
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=2, n_heads=2,
                           n_kv_heads=1, d_head=128, qk_norm="head",
                           remat=False, **kw)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    # gains away from their initial ones, so that their gradients and
    # their place in the chain are read
    for name in ("q_norm", "k_norm"):
        params["layers"][name] = 1 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), params["layers"][name].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    return cfg, params, {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


@pytest.fixture(scope="module")
def seam_on_the_expressions():
    """-> (cfg, params, batch, loss and gradients by the expressions,
    no kernel in the program)."""
    cfg, params, batch = _seam_model()
    return cfg, params, batch, _loss_and_grads(cfg, params, batch)


@pytest.mark.parametrize("mode", [False, "attn", "attn+gate+qkv"])
def test_seam_kernels_match_the_expressions(mode, seam_on_the_expressions,
                                            monkeypatch):
    """A ``qk_norm="head"`` model with the seam on its kernel pair
    (interpret mode; the attention behind it on the reference math, fed
    head-major) against the expressions ``_head_proj`` + ``_rope``: loss
    and every gradient, under no remat and under the two modes that name
    what the seam hands on (``rope_q``, ``rope_k``, ``attn_v``). The
    file's bounds, the absolute one a millionth of a leaf's largest
    value where that is above 1 (the embedding's gradient sums many
    tokens a row: 1.7 at the largest, 1.5e-6 off in one element of
    16,384, where the kernels' backward adds in another order)."""
    from horovod_tpu.ops import qk_prep

    cfg, params, batch, ref = seam_on_the_expressions
    monkeypatch.setattr(qk_prep, "_INTERPRET", True)
    monkeypatch.setattr(qk_prep, "TOKENS_A_STEP", 16)
    _assert_same_loss_and_grads(
        _loss_and_grads(dataclasses.replace(cfg, remat=mode), params, batch),
        ref, atol=lambda b: 1e-6 * max(1.0, float(jnp.abs(b).max())))


def test_seam_kernels_hand_the_remat_policies_their_names(monkeypatch):
    """``attn+gate+qkv`` saves the seam's outputs by name, head-major
    now: with the kernels on, the backward of a layer runs
    ``hvd_qk_prep_fwd`` once under ``attn`` (the recomputation) and not
    at all under ``attn+gate+qkv``."""
    from horovod_tpu.ops import qk_prep

    cfg, params, batch = _seam_model()
    monkeypatch.setattr(qk_prep, "_INTERPRET", True)

    def forward_calls(mode):
        c = dataclasses.replace(cfg, remat=mode, n_layers=1)
        p = jax.tree.map(lambda a: a, params)
        p["layers"] = jax.tree.map(lambda a: a[:1], params["layers"])
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: llama_loss(p, batch, c)))(p)
        return str(jaxpr).count("hvd_qk_prep_fwd"), \
            str(jaxpr).count("hvd_qk_prep_bwd")

    assert forward_calls("attn") == (2, 1)
    assert forward_calls("attn+gate+qkv") == (1, 1)
