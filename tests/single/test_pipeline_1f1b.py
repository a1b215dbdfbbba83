"""1F1B pipeline schedule (parallel.pipeline.one_f_one_b) vs GPipe.

The two schedules compute the same mathematical function — gpipe as
all-forwards + AD's reversed scan, 1f1b as a manual interleaved
forward/backward with the loss fused into the last stage — so loss AND
gradients must agree with each other and with the unsharded reference.
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
from horovod_tpu.parallel.sharding import apply_sharding, named_sharding


def _skip_unless_8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


def _setup(cfg, batch_shape=(4, 16), seed=1, with_mask=False):
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(seed), batch_shape, 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    if with_mask:
        mask = jnp.ones(batch_shape).at[1, 10:].set(0)
        batch["mask"] = mask
    return params, batch


def _pipe_loss_and_grads(cfg, params, batch, mesh):
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), "seq"))
    return jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, b_sh, cfg, mesh)))(p_sh)


@pytest.mark.parametrize("with_mask", [False, True])
def test_1f1b_matches_gpipe_and_reference(with_mask):
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False)
    cfg_1 = dataclasses.replace(cfg_g, pipeline_schedule="1f1b")
    params, batch = _setup(cfg_g, with_mask=with_mask)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg_g)))(params)

    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    gp_loss, gp_grads = _pipe_loss_and_grads(cfg_g, params, batch, mesh)
    ob_loss, ob_grads = _pipe_loss_and_grads(cfg_1, params, batch, mesh)

    np.testing.assert_allclose(float(gp_loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(float(ob_loss), float(ref_loss), rtol=1e-5)
    for (ka, a), (_, b_), (_, c_) in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree_util.tree_leaves_with_path(gp_grads),
            jax.tree_util.tree_leaves_with_path(ob_grads)):
        np.testing.assert_allclose(
            np.asarray(c_), np.asarray(a), rtol=2e-4, atol=1e-6,
            err_msg=f"1f1b vs reference: {jax.tree_util.keystr(ka)}")
        np.testing.assert_allclose(
            np.asarray(c_), np.asarray(b_), rtol=2e-4, atol=1e-6,
            err_msg=f"1f1b vs gpipe: {jax.tree_util.keystr(ka)}")


def test_1f1b_moe_matches_gpipe():
    """MoE through 1f1b: the aux objective folded via its constant
    cotangent must reproduce the gpipe path's loss + w*mean(aux) — the
    router gradients are the sensitive part."""
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny_moe(dtype="float32", n_layers=4,
                                 remat=False, moe_impl="gshard")
    cfg_1 = dataclasses.replace(cfg_g, pipeline_schedule="1f1b")
    params, batch = _setup(cfg_g)

    mesh = parallel.create_mesh(pipe=2, expert=2, tensor=2,
                                devices=jax.devices()[:8])
    gp_loss, gp_grads = _pipe_loss_and_grads(cfg_g, params, batch, mesh)
    ob_loss, ob_grads = _pipe_loss_and_grads(cfg_1, params, batch, mesh)

    np.testing.assert_allclose(float(ob_loss), float(gp_loss), rtol=1e-5)
    for (ka, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(gp_grads),
            jax.tree_util.tree_leaves_with_path(ob_grads)):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(ka))


def test_1f1b_more_microbatches_than_stages():
    """M > S exercises the stash-reuse path (Q < M slots wrap around)."""
    _skip_unless_8()
    cfg_g = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                             pipeline_microbatches=8)
    cfg_1 = dataclasses.replace(cfg_g, pipeline_schedule="1f1b")
    params, batch = _setup(cfg_g, batch_shape=(8, 16))

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg_g)))(params)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    ob_loss, ob_grads = _pipe_loss_and_grads(cfg_1, params, batch, mesh)
    np.testing.assert_allclose(float(ob_loss), float(ref_loss), rtol=1e-5)
    for (ka, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree_util.tree_leaves_with_path(ob_grads)):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(ka))


def test_1f1b_bf16_compiles_on_cpu():
    """bf16 activations through the 1f1b schedule must not hit XLA
    CPU's AllReducePromotion crash (the shared-psum f32 guards)."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(n_layers=4, remat=False,
                           pipeline_schedule="1f1b")  # default bf16
    params, batch = _setup(cfg)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    loss, grads = _pipe_loss_and_grads(cfg, params, batch, mesh)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_unknown_pipeline_schedule_rejected():
    cfg = LlamaConfig.tiny(dtype="float32", pipeline_schedule="bogus")
    params, batch = _setup(cfg)
    with pytest.raises(ValueError, match="pipeline_schedule"):
        llama_loss(params, batch, cfg)


def test_1f1b_value_only_routes_through_gpipe_and_matches():
    """A no-grad llama_loss call under pipeline_schedule="1f1b" runs
    the custom_vjp PRIMAL — the gpipe forward + loss head (one forward,
    no gradients; ADVICE r5) — and its value must match the
    differentiated path's loss."""
    _skip_unless_8()
    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, remat=False,
                           pipeline_schedule="1f1b")
    params, batch = _setup(cfg, with_mask=True)
    mesh = parallel.create_mesh(pipe=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    p_sh = apply_sharding(
        params, parallel.shard_params(params, mesh,
                                      llama_partition_rules(pipeline=True)))
    b_sh = jax.device_put(
        batch, named_sharding(mesh, ("data", "fsdp"), "seq"))
    value_only = jax.jit(lambda p: llama_loss(p, b_sh, cfg, mesh))(p_sh)
    grad_loss, _ = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, b_sh, cfg, mesh)))(p_sh)
    np.testing.assert_allclose(float(value_only), float(grad_loss),
                               rtol=1e-5)
