"""OLMoE through the normal llama path against the plain float32
reference (horovod_tpu/models/reference.py): logits, loss, every
gradient leaf, prefill then cached decode, the remat modes, and the
load-balancing term against a hand count. Small sizes, CPU.

The tolerance. Program and reference both compute in float32 here
(``dtype="float32"``, float32 parameters); they differ only in the order
of float32 additions (blockwise attention against a full softmax, a
grouped contraction against a dense one). Measured on these sizes: 6e-7
of the largest logit, 1.3e-6 of the largest entry of a gradient leaf
(float32's epsilon is 1.2e-7). The bound is 2e-5, fifteen times the
widest reading, room for another BLAS's summation order. bf16 compute (8
mantissa bits: 4e-3 a rounding) reads 4e-2 on the logits and fails it
two thousand times over: the last case of
``test_logits_loss_and_every_gradient_leaf`` checks that it does. A
missing q/k norm, a renormalised top-k or a top-1 aux count each move
the result by whole percents: the norm gains are drawn away from 1 so
that they do.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import (
    llama_forward,
    moe_balance_loss,
    moe_route,
)
from horovod_tpu.models.reference import olmoe_forward, olmoe_loss

TOL = 2e-5
DENSE_LOSS_AT_PARENT = 5.903080940246582


def _cfg(**kw):
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=32, rope_theta=10000.0, n_experts=8,
                n_experts_per_token=3, norm_topk_prob=False, qk_norm=True,
                dtype="float32", param_dtype="float32", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    """Seeded weights; every norm gain drawn from [0.5, 1.5] so that a
    norm left out, or applied per head, changes the result."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))

    def away_from_one(x):
        return jax.random.uniform(next(keys), x.shape, x.dtype, 0.5, 1.5)

    for name in list(params["layers"]):
        if name.endswith("norm"):
            params["layers"][name] = away_from_one(params["layers"][name])
    params["final_norm"] = away_from_one(params["final_norm"])
    return params


def _batch(cfg, shape=(2, 16), seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _err(got, ref):
    """Largest difference as a share of the reference's largest entry."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


# Compiled once a configuration (``cfg`` static), as the cell runs them:
# called eagerly, jax compiles these programs a primitive at a time. The
# EAGER call, which users make too, stays in
# ``test_the_dense_configuration_is_what_it_was``.
_forward = jax.jit(llama_forward, static_argnums=2)
_ref_forward = jax.jit(olmoe_forward, static_argnums=2)
_loss_and_grads = jax.jit(jax.value_and_grad(llama_loss), static_argnums=2)
# the decode path's two halves, the layer and the position as data
_prefill = jax.jit(gen._prefill, static_argnums=(2, 3))
_attend_step = jax.jit(gen._attend_step, static_argnums=2)
_lm_logits = jax.jit(gen._lm_logits, static_argnums=2)


def _all_readings(forward, loss):
    """Logits, loss and gradients as ONE program a configuration."""
    return jax.jit(lambda params, batch, cfg: (
        forward(params, batch["tokens"], cfg),
        jax.value_and_grad(loss)(params, batch, cfg)), static_argnums=2)


_readings = _all_readings(llama_forward, llama_loss)
_ref_readings = _all_readings(olmoe_forward, olmoe_loss)


@pytest.mark.parametrize("norm_topk_prob, dtype, agrees", [
    (False, "float32", True),      # OLMoE as published
    (True, "float32", True),       # renormalised top-k, same path
    (False, "bfloat16", False),    # what the bound is there to refuse
])
def test_logits_loss_and_every_gradient_leaf(norm_topk_prob, dtype,
                                             agrees):
    cfg = _cfg(norm_topk_prob=norm_topk_prob, dtype=dtype)
    params, batch = _params(cfg), _batch(cfg)
    logits, (loss, grads) = _readings(params, batch, cfg)
    (ref_logits, _), (ref_loss, ref_grads) = _ref_readings(params, batch,
                                                           cfg)
    errs = {"logits": _err(logits, ref_logits),
            "loss": _err(loss, ref_loss)}
    assert set(grads["layers"]) == set(ref_grads["layers"]) \
        >= {"q_norm", "k_norm", "router", "moe_gate"}
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        errs[jax.tree_util.keystr(path)] = _err(g, r)
    if agrees:
        assert max(errs.values()) < TOL, errs
    else:
        assert errs["logits"] > 100 * TOL, errs


def test_norm_topk_prob_changes_the_result():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    renorm = dataclasses.replace(cfg, norm_topk_prob=True)
    a = _forward(params, batch["tokens"], cfg)
    b = _forward(params, batch["tokens"], renorm)
    assert _err(a, b) > 1e-2
    assert _err(_ref_forward(params, batch["tokens"], renorm)[0], b) < TOL


def test_aux_term_against_a_hand_count():
    # Three tokens, four experts, two choices each; h picks a row of the
    # router, so the logits below ARE the router's.
    logits = np.array([[2.0, 1.0, 0.0, -1.0],     # chooses 0 then 1
                       [0.0, 3.0, -1.0, 1.0],     # chooses 1 then 3
                       [1.0, 0.0, 2.0, -2.0]],    # chooses 2 then 0
                      np.float32)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    vals, idx, balance = moe_route(jnp.eye(3), jnp.asarray(logits), 2,
                                   norm_topk_prob=False)
    assert np.asarray(idx).tolist() == [[0, 1], [1, 3], [2, 0]]
    np.testing.assert_allclose(vals, [[probs[0, 0], probs[0, 1]],
                                      [probs[1, 1], probs[1, 3]],
                                      [probs[2, 2], probs[2, 0]]],
                               rtol=1e-6)
    # experts 0 and 1 were chosen by two of three tokens, 2 and 3 by one
    chosen = np.array([2, 2, 1, 1]) / 3
    np.testing.assert_allclose(balance, [chosen, probs.mean(0)], rtol=1e-6)
    np.testing.assert_allclose(moe_balance_loss(balance),
                               4 * np.sum(chosen * probs.mean(0)),
                               rtol=1e-6)
    # Renormalised, the two values of a token sum to 1; same choices.
    vals_n, idx_n, _ = moe_route(jnp.eye(3), jnp.asarray(logits), 2)
    assert np.asarray(idx_n).tolist() == np.asarray(idx).tolist()
    np.testing.assert_allclose(np.asarray(vals_n).sum(-1), 1.0, rtol=1e-6)
    # Two layers: the statistics are pooled BEFORE the product, as the
    # published loss pools all layers' tokens.
    other = jnp.asarray([[0.7, 0.1, 0.1, 0.1], [0.25, 0.25, 0.25, 0.25]])
    pooled = (np.asarray(balance) + np.asarray(other)) / 2
    np.testing.assert_allclose(
        moe_balance_loss(jnp.stack([balance, other])),
        4 * np.sum(pooled[0] * pooled[1]), rtol=1e-6)
    assert float(moe_balance_loss(jnp.zeros((5, 2, 0)))) == 0.0   # dense


@pytest.mark.parametrize("batch", [2, 4])
def test_prefill_then_cached_decode_against_the_full_forward(batch):
    """Prefill 12 tokens, decode 4 more through the cache, each step's
    logits against the reference's forward over the whole sequence.
    Batch 2 decodes through the top-k expert gather (2 x 3 slots < 8
    experts), batch 4 through the grouped dispatch."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = _batch(cfg, (batch, 16))["tokens"]
    t0, n_new = 12, 4
    ref, _ = _ref_forward(params, tokens, cfg)

    x, cache_k, cache_v = _prefill(params, tokens[:, :t0], cfg, n_new)
    assert _err(_lm_logits(params, x, cfg), ref[:, :t0]) < TOL
    for pos in range(t0, t0 + n_new):
        x = params["embed"][tokens[:, pos]]
        for li in range(cfg.n_layers):
            lp = jax.tree.map(lambda w: w[li], params["layers"])
            x, cache_k, cache_v = _attend_step(
                x, lp, cfg, cache_k, cache_v, li, jnp.int32(pos))
        assert _err(_lm_logits(params, x, cfg), ref[:, pos]) < TOL, pos


@pytest.mark.parametrize("remat", ["attn", "attn+moe", "moe", "full"])
def test_remat_modes_give_the_same_loss_and_gradients(remat):
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = _loss_and_grads(params, batch, cfg)
    loss_r, grads_r = _loss_and_grads(
        params, batch, dataclasses.replace(cfg, remat=remat))
    np.testing.assert_allclose(loss_r, loss, rtol=1e-6)
    # Every leaf to the bounds this test has always had, but the
    # embedding's gradient: since the gate weight scales the expert
    # activations, XLA's CPU fusions round silu * up * w differently
    # where remat re-runs them, and the sum of every token's dh into
    # the embedding reads 4.4e-7 (0.9 float32 ulp of its largest
    # element, 5.72) over rtol * |b| at entries near zero, in every
    # mode. Its atol is 2 ulps of that element.
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads_r),
                            jax.tree.leaves(grads)):
        atol = 1e-7
        if jax.tree_util.keystr(path) == "['embed']":
            atol = 2 * np.spacing(np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=atol)


def test_the_dense_configuration_is_what_it_was():
    """No q/k-norm or router leaf where the config has none, and the
    loss of a seeded dense model is the value read at the commit before
    the q/k norm and the new router existed (4d73205, float32, CPU).
    By the EAGER calls of ``llama_forward`` and ``llama_loss``: this
    file's one case that runs the model a primitive at a time, as a
    user without ``jax.jit`` does."""
    cfg = LlamaConfig.tiny(dtype="float32", remat=False)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert sorted(params["layers"]) == [
        "attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
        "wq", "wv"]
    batch = _batch(cfg)
    logits, aux = llama_forward(params, batch["tokens"], cfg,
                                return_aux=True)
    assert float(aux) == 0.0
    np.testing.assert_allclose(llama_loss(params, batch, cfg),
                               DENSE_LOSS_AT_PARENT, rtol=1e-6)

