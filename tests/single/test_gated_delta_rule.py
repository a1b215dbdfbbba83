"""The chunked gated delta rule (``ops/gated_delta_rule.py``) against
the recurrence token by token as it is written: forward and all five
gradients, for one, two and several chunks, weak and strong decays,
write strengths near 0 and near 1. Float32 on the CPU: the two differ in
the order of float32 additions (and the chunked form's triangular
solve), 2e-5 of the largest entry. Small sizes."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.gated_delta_rule import CHUNK, gated_delta_rule

pytestmark = pytest.mark.quick
TOL = 2e-5
NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


def token_by_token(q, k, v, g, beta):
    """``S <- exp(g) S; r = v - S^T k; S <- S + k (beta r)^T; o = S^T
    q`` from a zero state: one token after another (a ``lax.scan`` over
    tokens, so that it compiles in a second)."""
    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None, None] * S
        r = v - jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + jnp.einsum("bhk,bhv->bhkv", k, beta[..., None] * r)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    b, _, h, dk = q.shape
    _, out = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _operands(t, decay, write, seed=0, shape=(2, 3, 16, 24)):
    """Unit keys and scaled unit queries as the mixer hands them over;
    ``g`` about ``-decay`` a token, ``beta`` about ``write``."""
    b, h, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        x = jax.random.normal(key, (b, t, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    beta = {"near0": 0.02, "near1": 0.98}.get(write)
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, t, h))) \
        if beta is None else beta + 0.02 * (
            jax.random.uniform(ks[4], (b, t, h)) - 0.5)
    return (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -decay * jax.random.uniform(ks[3], (b, t, h), minval=0.5,
                                        maxval=1.5),
            beta, jax.random.normal(ks[5], (b, t, h, dv)))


def _weighted(rule):
    def f(q, k, v, g, beta, w):
        out = rule(q, k, v, g, beta)
        return jnp.sum(out * w), out
    return f


# jitted once: a shape compiles once for all its decays and strengths
_GRADS = {rule: jax.jit(jax.grad(_weighted(rule), argnums=(0, 1, 2, 3, 4),
                                 has_aux=True))
          for rule in (gated_delta_rule, token_by_token)}


def _readings(rule, *operands):
    grads, out = _GRADS[rule](*operands)
    return (out,) + grads


@pytest.mark.parametrize("write", ["near0", "near1", "mixed"])
@pytest.mark.parametrize("decay", [0.01, 5.0])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_form_is_the_recurrence(chunks, decay, write):
    operands = _operands(chunks * CHUNK, decay, write)
    with jax.default_matmul_precision("highest"):
        got = _readings(gated_delta_rule, *operands)
        ref = _readings(token_by_token, *operands)
    for name, a, b in zip(NAMES, got, ref):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < TOL, (name, err)


def test_the_state_carries_from_chunk_to_chunk():
    """With weak decay a late token's output depends on the first
    chunk's values: a scan that dropped its carry would not."""
    q, k, v, g, beta, _ = _operands(3 * CHUNK, 0.01, "mixed")
    rule = jax.jit(gated_delta_rule)
    out = rule(q, k, v, g, beta)
    moved = rule(q, k, v.at[:, :CHUNK].multiply(2.0), g, beta)
    assert float(jnp.max(jnp.abs(out - moved)[:, 2 * CHUNK:])) > 1e-3
    # and causal: an early output does not see a later token
    later = rule(q, k, v.at[:, 2 * CHUNK:].multiply(2.0), g, beta)
    assert float(jnp.max(jnp.abs(out - later)[:, :2 * CHUNK])) == 0.0


def test_bf16_operands_accumulate_in_float32():
    operands = _operands(2 * CHUNK, 0.3, "mixed")
    low = tuple(x.astype(jnp.bfloat16) for x in operands[:3]) \
        + operands[3:5]
    got = gated_delta_rule(*low)
    assert got.dtype == jnp.bfloat16
    ref = token_by_token(*(x.astype(jnp.float32) for x in low))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                / jnp.max(jnp.abs(ref)))
    assert err < 2e-2, err


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    q, k, v, g, beta, _ = _operands(CHUNK, 0.1, "mixed")
    with pytest.raises(ValueError, match="chunks of 64"):
        gated_delta_rule(q[:, :40], k[:, :40], v[:, :40], g[:, :40],
                         beta[:, :40])
    # a chunk the caller names divides it
    out = gated_delta_rule(q[:, :48], k[:, :48], v[:, :48], g[:, :48],
                           beta[:, :48], chunk=16)
    ref = token_by_token(q[:, :48], k[:, :48], v[:, :48], g[:, :48],
                         beta[:, :48])
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
