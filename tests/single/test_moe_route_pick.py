"""``moe_route`` picks its K scores, and hands their gradient back, by
comparing the chosen indices with an iota over the experts and selecting
under a sum (``models/llama.py:_pick``, ``_unpick``): no gather and no
scatter of scalars, which cost the chip 7-19 ns an element (PERF.md
section 6, PR 54). top-k's indices are distinct, so each sum has one
term: the values are the gather's and the scatter's to the bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import llama
from horovod_tpu.models.llama import moe_route

pytestmark = pytest.mark.quick

TOKENS, D = (2, 160), 32

# The benchmark's routers: OLMoE, Trinity-Mini, LFM2, Qwen3-Next,
# Nemotron (E, K, score, bias, norm, scale).
ROUTERS = [
    pytest.param(64, 8, "softmax", False, False, 1.0, id="olmoe"),
    pytest.param(128, 8, "sigmoid", True, True, 2.826, id="trinitymini"),
    pytest.param(32, 4, "sigmoid", True, True, 1.0, id="lfm2"),
    pytest.param(512, 10, "softmax", False, True, 1.0, id="qwen3next"),
    pytest.param(512, 22, "sigmoid", True, True, 5.0, id="nemotron"),
]


def _gathered_route(h, router_w, k, norm, score, bias, scale):
    """The router as it stood before PR 54, ``take_along_axis`` and its
    own VJP's scatter: the reference."""
    E = router_w.shape[-1]
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    choose_by = probs if bias is None else probs + lax.stop_gradient(bias)
    idx = lax.stop_gradient(lax.top_k(choose_by, k)[1])
    vals = jnp.take_along_axis(probs, idx, axis=-1)
    if norm:
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        vals = vals * scale
    lead = tuple(range(probs.ndim - 1))
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(-2)
    return vals, idx, jnp.stack([chosen.mean(lead), probs.mean(lead)])


def _inputs(E, K, with_bias):
    kh, kw, kb, kg, kt = jax.random.split(jax.random.PRNGKey(E + K), 5)
    h = jax.random.normal(kh, TOKENS + (D,), jnp.float32)
    router_w = jax.random.normal(kw, (D, E), jnp.float32) / D ** 0.5
    bias = (0.1 * jax.random.normal(kb, (E,), jnp.float32)
            if with_bias else None)
    weights = jax.random.normal(kg, TOKENS + (K,), jnp.float32)
    tilt = jax.random.normal(kt, (E,), jnp.float32)
    return h, router_w, bias, weights, tilt


def _loss(route, args, weights, tilt):
    def f(h, router_w):
        vals, idx, balance = route(h, router_w, *args)
        return ((vals * weights).sum() + (balance[1] * tilt).sum(),
                (vals, idx, balance))
    return f


@pytest.mark.parametrize("E, K, score, with_bias, norm, scale", ROUTERS)
def test_the_pick_and_its_transpose_are_the_gather_and_the_scatter(
        E, K, score, with_bias, norm, scale):
    h, router_w, bias, g, _ = _inputs(E, K, with_bias)
    probs = jax.random.uniform(jax.random.PRNGKey(K), TOKENS + (E,))
    # operands as ARGUMENTS: closed over they are constants, and the
    # compiler folds the router (a sort of 320 rows of E) in its evaluator
    idx = jax.jit(lambda h, w, b: moe_route(
        h, w, K, norm, score, b, scale)[1])(h, router_w, bias)

    want, scatter = jax.vjp(
        lambda p: jnp.take_along_axis(p, idx, axis=-1), probs)
    got, put_back = jax.vjp(lambda p: llama._pick(p, idx), probs)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(put_back(g)[0], scatter(g)[0])
    # ``_top_k``'s VJP: the same scatter at the indices it chose itself.
    (vals, own_idx), top_k_bwd = jax.vjp(lambda p: llama._top_k(p, K), probs)
    np.testing.assert_array_equal(vals, lax.top_k(probs, K)[0])
    want = jax.linear_transpose(
        lambda p: jnp.take_along_axis(p, own_idx, axis=-1), probs)(g)[0]
    np.testing.assert_array_equal(
        top_k_bwd((g, np.zeros(own_idx.shape, jax.dtypes.float0)))[0], want)


@pytest.mark.parametrize("E, K, score, with_bias, norm, scale", ROUTERS)
def test_the_router_equals_its_gathered_form(E, K, score, with_bias, norm,
                                             scale):
    """Weights, choice, statistics and both gradients, ``==`` in
    float32: the dense pick is the same work, not other work. Evaluated
    a primitive at a time: under one ``jit`` the CPU's compiler fuses
    the sigmoid into the select and rounds it otherwise than beside a
    gather, a last bit that is not the pick's."""
    h, router_w, bias, weights, tilt = _inputs(E, K, with_bias)
    args = (K, norm, score, bias, scale)

    def through(route):
        return jax.value_and_grad(
            _loss(route, args, weights, tilt), argnums=(0, 1),
            has_aux=True)(h, router_w)

    (loss, out), grads = through(moe_route)
    (ref_loss, ref), ref_grads = through(_gathered_route)
    assert out[0].dtype == jnp.float32 and out[1].dtype == jnp.int32
    for got, want in zip(out + grads, ref + ref_grads):
        np.testing.assert_array_equal(got, want)
    assert loss == ref_loss and float(jnp.abs(grads[1]).max()) > 0


@pytest.mark.parametrize("E, K, score, with_bias, norm, scale", ROUTERS)
def test_a_bias_that_changes_the_choice_changes_no_weights_value(
        E, K, score, with_bias, norm, scale):
    """Every cell's router under a bias large enough to move the choice
    (the softmax routers' too: the branch is ``bias is not None``): the
    weights are still the unbiased scores at the experts chosen."""
    h, router_w, _, _, _ = _inputs(E, K, True)
    bias = jnp.where(jnp.arange(E) % 3 == 0, 0.5, -0.5).astype(jnp.float32)
    def route(bias):             # a primitive at a time, as above
        return moe_route(h, router_w, K, False, score, bias, 1.0)

    vals, idx, _ = route(bias)
    plain_vals, plain_idx, _ = route(jnp.zeros(E, jnp.float32))
    assert (np.sort(idx, -1) != np.sort(plain_idx, -1)).any()
    logits = h @ router_w
    probs = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    np.testing.assert_array_equal(
        vals, jnp.take_along_axis(probs, idx, axis=-1))
    np.testing.assert_array_equal(
        plain_vals, jnp.take_along_axis(probs, plain_idx, axis=-1))


@pytest.mark.parametrize("E, K, score, with_bias, norm, scale", ROUTERS)
def test_the_gradient_is_the_same_with_the_choice_saved_and_recomputed(
        E, K, score, with_bias, norm, scale):
    h, router_w, bias, weights, tilt = _inputs(E, K, with_bias)
    f = _loss(moe_route, (K, norm, score, bias, scale), weights, tilt)

    def grads(policy):
        g = jax.checkpoint(lambda h, w: f(h, w)[0], policy=policy)
        return jax.grad(g, argnums=(0, 1))(h, router_w)

    plain = jax.grad(lambda h, w: f(h, w)[0], (0, 1))(h, router_w)
    saved = grads(jax.checkpoint_policies.save_only_these_names(
        "moe_gate_idx"))
    recomputed = grads(jax.checkpoint_policies.nothing_saveable)
    for a, b, c in zip(plain, saved, recomputed):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


@pytest.mark.parametrize("with_bias", [False, True],
                         ids=["top_k_values", "biased_choice"])
def test_the_routers_gradient_lowers_with_no_gather_and_no_scatter(
        with_bias):
    E, K = 32, 4
    h, router_w, bias, weights, tilt = _inputs(E, K, with_bias)
    f = _loss(moe_route, (K, True, "sigmoid" if with_bias else "softmax",
                          bias, 1.0), weights, tilt)
    text = jax.jit(jax.grad(lambda h, w: f(h, w)[0], (0, 1))).lower(
        h, router_w).as_text()
    assert "stablehlo.select" in text and "top_k" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
