"""ResNet + BERT model families: shapes, semantics, sharded train step.

Reference analog: the reference validates its benchmark models by
training them end-to-end in examples; here they are library code so they
get unit tests (same pattern as test_llama.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import parallel
from horovod_tpu.models import (
    BertConfig,
    ResNetConfig,
    bert_forward,
    bert_init,
    bert_mlm_loss,
    bert_partition_rules,
    resnet_forward,
    resnet_init,
    resnet_loss,
)
from horovod_tpu.parallel.sharding import apply_sharding, named_sharding


# ---- resnet ----

def _tiny_resnet(depth=18):
    return ResNetConfig(depth=depth, num_classes=7, width=8,
                        compute_dtype="float32")


# Compiled once a configuration: called eagerly, jax compiles a ResNet
# a primitive at a time (25-33 s a case where these take a few).
_init = jax.jit(resnet_init, static_argnums=0)
_forward = jax.jit(resnet_forward, static_argnums=3,
                   static_argnames="train")


def test_resnet_forward_shapes():
    """By the EAGER calls of ``resnet_init`` and ``resnet_forward``,
    which users make too: this file's one ResNet case that runs a
    primitive at a time."""
    cfg = _tiny_resnet()
    params, state = resnet_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits, new_state = resnet_forward(params, state, x, cfg, train=True)
    assert logits.shape == (2, 7)
    assert logits.dtype == jnp.float32
    # Training updates running stats away from init.
    stem = new_state["stem"]["bn"]
    assert not np.allclose(np.asarray(stem["mean"]), 0.0)


def test_resnet_bottleneck_variant():
    cfg = _tiny_resnet(depth=50)
    params, state = _init(cfg, jax.random.PRNGKey(0))
    assert "conv3" in params["stage0"][0]  # bottleneck blocks
    x = jnp.zeros((1, 32, 32, 3))
    logits, _ = _forward(params, state, x, cfg, train=False)
    assert logits.shape == (1, 7)


def test_resnet_eval_uses_running_stats():
    cfg = _tiny_resnet()
    params, state = _init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
    l1, s1 = _forward(params, state, x, cfg, train=False)
    # eval must not mutate state
    assert np.allclose(np.asarray(s1["stem"]["bn"]["mean"]),
                       np.asarray(state["stem"]["bn"]["mean"]))


def test_resnet_train_step_decreases_loss():
    cfg = _tiny_resnet()
    params, state = resnet_init(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.5)
    opt = tx.init(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 32, 32, 3))
    y = jnp.arange(8) % 7
    batch = {"images": x, "labels": y}

    @jax.jit
    def step(params, state, opt):
        (loss, state), grads = jax.value_and_grad(
            resnet_loss, has_aux=True)(params, state, batch, cfg)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), state, opt, loss

    losses = []
    for _ in range(5):
        params, state, opt, loss = step(params, state, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# ---- bert ----

def test_bert_forward_shapes():
    cfg = BertConfig.tiny(dtype="float32")
    params = bert_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = bert_forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_bert_bidirectional():
    # Unlike llama, changing a LATER token changes EARLIER logits.
    cfg = BertConfig.tiny(dtype="float32")
    params = bert_init(cfg, jax.random.PRNGKey(0))
    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 7].set(5)
    l1 = bert_forward(params, t1, cfg)
    l2 = bert_forward(params, t2, cfg)
    assert not np.allclose(np.asarray(l1[0, 0]), np.asarray(l2[0, 0]))


def test_bert_padding_masked_out():
    # Logits at real positions must ignore padding tokens' content.
    cfg = BertConfig.tiny(dtype="float32")
    params = bert_init(cfg, jax.random.PRNGKey(0))
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]])
    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 6].set(9)  # change only a padded position
    l1 = bert_forward(params, t1, cfg, attention_mask=mask)
    l2 = bert_forward(params, t2, cfg, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(l1[0, :4]), np.asarray(l2[0, :4]),
                               atol=1e-5)


def test_bert_fully_padded_sample_no_nan():
    # A ragged final batch pads with empty sequences: attention_mask all
    # zero for that sample. The loss must stay finite (regression: -inf
    # mask bias made softmax NaN and poisoned the whole batch).
    cfg = BertConfig.tiny(dtype="float32")
    params = bert_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 8), jnp.int32)
    mask = jnp.array([[1] * 8, [0] * 8])
    mlm = jnp.array([[1.0] * 8, [0.0] * 8])
    batch = {"tokens": tokens, "targets": tokens, "mlm_mask": mlm,
             "attention_mask": mask}
    loss = bert_mlm_loss(params, batch, cfg)
    assert jnp.isfinite(loss)


def test_bert_pos_embed_partition_rule():
    # pos_embed must hit its own rule, not the tied-embedding rule
    # (regression: r"embed$" shadowed r"pos_embed").
    import re
    rules = bert_partition_rules()
    first = next(spec for pat, spec in rules if re.search(pat, "pos_embed"))
    from jax.sharding import PartitionSpec as P
    assert first == P(None, "fsdp")
    tied = next(spec for pat, spec in rules if re.search(pat, "embed"))
    assert tied == P("tensor", "fsdp")


def test_bert_mlm_loss_finite_and_masked():
    cfg = BertConfig.tiny(dtype="float32")
    params = bert_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mlm_mask": jnp.zeros((2, 16)).at[:, :4].set(1)}
    loss = bert_mlm_loss(params, batch, cfg)
    assert jnp.isfinite(loss)
    # With no predicted positions, loss is exactly 0 (div guarded).
    batch0 = dict(batch, mlm_mask=jnp.zeros((2, 16)))
    assert float(bert_mlm_loss(params, batch0, cfg)) == 0.0


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_bert_sharded_train_step():
    cfg = BertConfig.tiny(dtype="float32", d_model=64, n_heads=4)
    mesh = parallel.create_mesh(data=2, fsdp=2, tensor=2,
                                devices=jax.devices()[:8])
    params = bert_init(cfg, jax.random.PRNGKey(0))
    shardings = parallel.shard_params(params, mesh, bert_partition_rules())
    params = apply_sharding(params, shardings)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mlm_mask": jnp.ones((4, 16))}
    batch = jax.device_put(batch, named_sharding(mesh, ("data", "fsdp")))

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(bert_mlm_loss)(params, batch, cfg)
        updates, opt = tx.update(grads, opt, params)
        return loss, optax.apply_updates(params, updates), opt

    loss, params, opt = step(params, opt, batch)
    assert jnp.isfinite(loss)
