"""Interleaved virtual-stage 1F1B (parallel.pipeline) vs 1F1B / GPipe /
the unsharded reference.

Two layers of pinning:

- schedule-table tests run the host-side list scheduler alone
  (build_interleaved_schedule) — slot counts, bubble fractions, the
  >=1.5x V=1 -> V=2 bubble shrink the round-6 acceptance bar names,
  ragged ``M % (S*V)`` remainders;
- gradient-equivalence tests run the full llama path through the
  partial-manual ``jax.shard_map`` of ``pipeline._pipe_spmd``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import parallel
from horovod_tpu.models import (
    LlamaConfig,
    llama_init,
    llama_loss,
    llama_partition_rules,
)
from horovod_tpu.parallel import pipeline
from horovod_tpu.parallel.pipeline import build_interleaved_schedule
from horovod_tpu.parallel.sharding import apply_sharding, named_sharding

pytestmark = pytest.mark.quick  # make test-quick runs the pipeline lane


def _skip_unless_8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


# ---- schedule tables (host-side, no devices needed) ------------------

def test_v1_reduces_to_true_1f1b():
    """V=1 single-subtick slots: U = 2M + 2(S-1) — already below the
    lockstep one_f_one_b's effective 2*(M + 2(S-1)) subticks."""
    for S, M in [(2, 4), (4, 8), (4, 16), (8, 16)]:
        s = build_interleaved_schedule(S, 1, M)
        assert s.n_slots == 2 * M + 2 * (S - 1), (S, M, s.n_slots)


def test_bubble_hits_ideal_when_S_divides_M():
    for S, V, M in [(2, 2, 4), (4, 2, 8), (4, 4, 8), (4, 2, 16),
                    (8, 2, 16), (2, 4, 8)]:
        s = build_interleaved_schedule(S, V, M)
        assert s.n_slots == 2 * M * V + 2 * (S - 1), (S, V, M, s.n_slots)


def test_acceptance_bubble_shrink_v1_to_v2():
    """The round-6 bar: at S=4, M=8 the bubble fraction must shrink by
    >= 1.5x going V=1 -> V=2 (it shrinks 1.73x: 6/22 -> 6/38)."""
    b1 = build_interleaved_schedule(4, 1, 8).bubble_fraction
    b2 = build_interleaved_schedule(4, 2, 8).bubble_fraction
    assert b1 / b2 >= 1.5, (b1, b2)
    b4 = build_interleaved_schedule(4, 4, 8).bubble_fraction
    assert b2 > b4, (b2, b4)


def test_ragged_remainder_schedules_complete():
    """M % (S*V) != 0 (and M < S*V): the list scheduler must still
    place every subtick — build asserts dependency-safety internally —
    with only a graceful slot-count degradation."""
    for S, V, M in [(2, 2, 3), (4, 2, 9), (2, 4, 2), (3, 2, 5)]:
        s = build_interleaved_schedule(S, V, M)
        assert (s.kind != 2).sum() == 2 * S * M * V  # all work placed
        assert s.n_slots <= 2 * M * V + 2 * (S - 1) + S * V


def test_schedule_tables_are_consistent():
    """Every forward's output is delivered exactly once (except the
    last global stage's, consumed locally by the loss head), one ring
    hop after production."""
    S, V, M = 4, 2, 8
    s = build_interleaved_schedule(S, V, M)
    n_fwd = int(((s.kind == 0) | (s.kind == 3)).sum())
    assert n_fwd == S * M * V
    # the loss head runs exactly once per microbatch, on the last device
    assert int((s.kind == 3).sum()) == M
    assert ((s.kind[:, :-1] != 3).all())
    # each non-terminal forward feeds one rf_valid entry next slot
    assert int(s.rf_valid.sum()) == (S * V - 1) * M
    assert int(s.rb_valid.sum()) == (S * V - 1) * M


# ---- gradient equivalence through the llama path ---------------------

def _setup(cfg, batch_shape=(4, 16), seed=1, with_mask=False):
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(seed), batch_shape, 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    if with_mask:
        batch["mask"] = jnp.ones(batch_shape).at[1, 10:].set(0)
    return params, batch


def _pipe_loss_and_grads(cfg, params, batch, mesh):
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), "seq"))
    return jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, b_sh, cfg, mesh)))(p_sh)


def _assert_tree_close(ref, got, err=""):
    # atol 5e-6: the schedules sum per-microbatch grads in different
    # orders (f32 throughout), so near-zero leaves wobble at float eps.
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref),
            jax.tree_util.tree_leaves_with_path(got)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=5e-6,
            err_msg=f"{err}{jax.tree_util.keystr(ka)}")


@pytest.mark.parametrize("with_mask", [False, True])
def test_interleaved_matches_1f1b_gpipe_and_reference(with_mask):
    """S=2, V=2, M=4: the four-way pin the issue asks for."""
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                             pipeline_microbatches=4)
    cfg_1 = dataclasses.replace(cfg_g, pipeline_schedule="1f1b")
    cfg_i = dataclasses.replace(cfg_g,
                                pipeline_schedule="interleaved_1f1b",
                                pipeline_virtual_stages=2)
    params, batch = _setup(cfg_g, with_mask=with_mask)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg_g)))(params)

    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    gp_loss, gp_grads = _pipe_loss_and_grads(cfg_g, params, batch, mesh)
    ob_loss, ob_grads = _pipe_loss_and_grads(cfg_1, params, batch, mesh)
    il_loss, il_grads = _pipe_loss_and_grads(cfg_i, params, batch, mesh)

    for got in (gp_loss, ob_loss, il_loss):
        np.testing.assert_allclose(float(got), float(ref_loss),
                                   rtol=1e-5)
    _assert_tree_close(ref_grads, il_grads, "interleaved vs reference: ")
    _assert_tree_close(gp_grads, il_grads, "interleaved vs gpipe: ")
    _assert_tree_close(ob_grads, il_grads, "interleaved vs 1f1b: ")


def test_interleaved_moe_aux_matches_gpipe():
    """MoE through the interleaved schedule: the constant-cotangent aux
    folding must reproduce gpipe's loss + w*mean(aux) — router grads
    are the sensitive part."""
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny_moe(dtype="float32", n_layers=4,
                                 remat=False, moe_impl="gshard")
    cfg_i = dataclasses.replace(cfg_g,
                                pipeline_schedule="interleaved_1f1b",
                                pipeline_virtual_stages=2)
    params, batch = _setup(cfg_g)
    mesh = parallel.create_mesh(pipe=2, expert=2, tensor=2,
                                devices=jax.devices()[:8])
    gp_loss, gp_grads = _pipe_loss_and_grads(cfg_g, params, batch, mesh)
    il_loss, il_grads = _pipe_loss_and_grads(cfg_i, params, batch, mesh)
    np.testing.assert_allclose(float(il_loss), float(gp_loss), rtol=1e-5)
    _assert_tree_close(gp_grads, il_grads)


def test_interleaved_ragged_microbatch_remainder():
    """M=6 with S*V=4 (remainder 2): the ragged schedule must stay
    gradient-exact, not just complete."""
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                             pipeline_microbatches=6)
    cfg_i = dataclasses.replace(cfg_g,
                                pipeline_schedule="interleaved_1f1b",
                                pipeline_virtual_stages=2)
    params, batch = _setup(cfg_g, batch_shape=(6, 16))
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg_g)))(params)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    il_loss, il_grads = _pipe_loss_and_grads(cfg_i, params, batch, mesh)
    np.testing.assert_allclose(float(il_loss), float(ref_loss), rtol=1e-5)
    _assert_tree_close(ref_grads, il_grads)


def test_interleaved_bf16_compiles_on_cpu():
    """bf16 activations through the interleaved schedule must not hit
    XLA CPU's AllReducePromotion crash (the shared f32-psum guards)."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(n_layers=4, remat=False,  # default bf16
                           pipeline_schedule="interleaved_1f1b",
                           pipeline_virtual_stages=2)
    params, batch = _setup(cfg)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    loss, grads = _pipe_loss_and_grads(cfg, params, batch, mesh)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_value_only_call_never_runs_the_schedule(monkeypatch):
    """A no-grad llama_loss under "interleaved_1f1b" must route through
    the custom_vjp PRIMAL (gpipe forward + loss head) — the combined
    forward/backward engine computes every gradient just to discard
    them. Proven by counting engine invocations, not just by value
    equality."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                           pipeline_schedule="interleaved_1f1b",
                           pipeline_virtual_stages=2)
    params, batch = _setup(cfg, with_mask=True)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), "seq"))

    calls = []
    real = pipeline.interleaved_one_f_one_b

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pipeline, "interleaved_one_f_one_b", counting)

    value_only = llama_loss(p_sh, b_sh, cfg, mesh)
    assert not calls, "value-only call engaged the fwd/bwd engine"
    grad_loss, _ = jax.value_and_grad(
        lambda p: llama_loss(p, b_sh, cfg, mesh))(p_sh)
    assert calls, "grad call should engage the engine"
    np.testing.assert_allclose(float(value_only), float(grad_loss),
                               rtol=1e-5)


def test_interleaved_composes_with_split_train_step():
    """The r6 program structure end-to-end: split grad/apply jits with
    2-way microbatch gradient accumulation, each grad call running the
    interleaved schedule (its own M=2 pipeline microbatches inside) —
    loss and updated params must match the monolithic one-jit step."""
    _skip_unless_8()
    import optax

    from horovod_tpu.parallel import make_split_train_step

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                           pipeline_schedule="interleaved_1f1b",
                           pipeline_virtual_stages=2,
                           pipeline_microbatches=2)
    params, batch = _setup(cfg)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), "seq"))
    tx = optax.sgd(1e-1)

    def loss_fn(p, d):
        return llama_loss(p, d, cfg, mesh)

    @jax.jit
    def monolithic(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt = tx.update(grads, opt, params)
        return loss, optax.apply_updates(params, updates)

    ref_loss, ref_params = monolithic(p_sh, tx.init(p_sh), b_sh)

    ts = make_split_train_step(loss_fn, tx, microbatches=2)
    loss, (p2, _) = ts.step(ts.init(p_sh), b_sh)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_tree_close(ref_params, p2, "split vs monolithic: ")


def test_virtual_stages_config_validation():
    cfg = LlamaConfig.tiny(dtype="float32", pipeline_virtual_stages=2)
    params, batch = _setup(cfg)
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="pipeline_virtual_stages"):
        llama_loss(params, batch, cfg, mesh)
    # n_layers=2 cannot split into 2 stages x 2 chunks
    cfg_bad = LlamaConfig.tiny(dtype="float32", n_layers=2,
                               pipeline_schedule="interleaved_1f1b",
                               pipeline_virtual_stages=2)
    with pytest.raises(ValueError, match="n_layers"):
        llama_loss(params, batch, cfg_bad, mesh)
