"""The chunked gated delta rule (``ops/gated_delta_rule.py``) against
the recurrence token by token as it is written: forward and all five
gradients, for one, two and several chunks, weak and strong decays,
write strengths near 0 and near 1. Float32 on the CPU: the two differ in
the order of float32 additions (and the chunked form's triangular
solve), 2e-5 of the largest entry. Small sizes.

Everything downstream of the inverse has two carriers: the factor stage
in XLA and a ``lax.scan`` (the CPU's, and the reference here), and a
Pallas kernel pair that forms the factors where it carries the state (a
TPU's): the pair runs in interpret mode against the scan, values and all
six gradients of ``_rule_of_chunks``, the inverse's among them, and
against the recurrence through the whole rule."""

import functools

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import gated_delta_rule as module
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
    if write == "to2":      # over (0, 2): the eigenvalue along k in (-1, 1)
        beta = 2.0 * beta
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


@pytest.mark.parametrize("write", ["near0", "near1", "mixed", "to2"])
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


# ---------------------------------------------------------------------
# The kernel pair, interpreted, against the scan and the recurrence.
# ---------------------------------------------------------------------

@pytest.fixture
def kernels(monkeypatch):
    """-> ``take(heads)``: from then on the rule's state pass is the
    kernel pair in interpret mode, a grid step taking at most so many
    heads."""
    def take(heads):
        monkeypatch.setattr(module, "_INTERPRET", True)
        monkeypatch.setattr(module, "HEADS_A_STEP", heads)
    return take


@functools.partial(jax.jit, static_argnums=tuple(range(7)))
def _chunk_operands(b, n, h, c, dk, dv, dtype):
    """``_rule_of_chunks``'s six and a weight for ``o``. ``inv`` is any
    matrix, not an inverse: its cotangent is then checked entry by
    entry, above the diagonal too."""
    ks = jax.random.split(jax.random.PRNGKey(n), 7)

    def normal(key, *shape):
        return (0.3 * jax.random.normal(key, (b, n, h) + shape)
                ).astype(dtype)

    return (normal(ks[0], c, dk), normal(ks[1], c, dk), normal(ks[2], c, dv),
            jnp.cumsum(-0.2 * jax.random.uniform(ks[3], (b, n, h, c)), -1),
            jax.random.uniform(ks[4], (b, n, h, c)), normal(ks[5], c, c),
            normal(ks[6], c, dv))


@jax.jit
def _chunk_readings(*operands):
    """(o, and the gradients of sum(o * weight) in the six)."""
    *x, weight = operands

    def f(*x):
        o = module._rule_of_chunks(*x, module._decay(x[3]))
        return jnp.sum(o.astype(jnp.float32)
                       * weight.astype(jnp.float32)), o

    grads, o = jax.grad(f, argnums=tuple(range(6)), has_aux=True)(*x)
    return (o,) + grads


CHUNK_NAMES = ("o", "dq", "dk", "dv", "dgamma", "dbeta", "dinv")


def _on_both_carriers(kernels, heads, operands):
    """-> the readings on the scan and on the kernel pair."""
    _chunk_readings.clear_cache()     # the carrier is chosen in a trace
    ref = _chunk_readings(*operands)
    kernels(heads)
    _chunk_readings.clear_cache()
    return ref, _chunk_readings(*operands)


# (chunks, heads, heads a step): heads a step that divide the heads, and
# that do not (three by two, six by four: the largest divisor under it);
# one chunk and several, in both dtypes
@pytest.mark.parametrize("dtype,n,h,heads", [
    (jnp.float32, 3, 3, 2), (jnp.bfloat16, 3, 4, 2), (jnp.bfloat16, 5, 6, 4),
    (jnp.float32, 1, 2, 2), (jnp.bfloat16, 1, 3, 2), (jnp.float32, 4, 4, 4)])
def test_the_kernel_pair_is_the_scan(kernels, dtype, n, h, heads):
    """Same operands, same roundings, float32 state in both: the factor
    stage in XLA and the scan against the pair that forms the factors in
    VMEM. ``o`` to the last bit of the operands' dtype. The gradients
    in the operands' dtype are autodiff's but for where the pieces are
    added (``dq``, ``dk`` and ``dinv`` each have two or three: autodiff
    rounds each to the dtype and adds, the kernel adds in float32 and
    rounds once): to float32 rounding, or one step of bfloat16. The
    gates' are float32 row sums in another order. ``dk`` 16 beside
    ``dv`` 32."""
    ref, got = _on_both_carriers(
        kernels, heads, _chunk_operands(1, n, h, 16, 16, 32, dtype))
    for name, a, b in zip(CHUNK_NAMES, got, ref):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)))
        scale = float(jnp.max(jnp.abs(b)))
        step = 2.0 ** -7 if a.dtype != dtype and name in (
            "dq", "dk", "dinv") else 2e-6
        assert err <= (0.0 if name == "o" else step * scale), (name, err)


def test_the_kernels_cotangent_of_the_inverse_is_autodiffs(kernels):
    """``dinv = du bv^T + dw bk^T`` is written by hand in the backward
    kernel; autodiff of ``u = inv bv``, ``w = inv bk`` through the scan
    is the reference, float32, every entry of every chunk (the solve's
    own transpose, which takes it from there, stays autodiff's)."""
    operands = _chunk_operands(2, 3, 2, 16, 16, 32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref, got = _on_both_carriers(kernels, 2, operands)
    dinv, want = got[-1], ref[-1]
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    assert float(jnp.max(jnp.abs(dinv - want))) \
        <= 2e-6 * float(jnp.max(jnp.abs(want)))
    # above the diagonal too: the kernel takes ``inv`` as it comes
    upper = jnp.triu(jnp.ones((16, 16), bool), 1)
    assert float(jnp.max(jnp.abs(jnp.where(upper, want, 0.0)))) > 1e-2


def test_a_step_takes_a_divisor_of_the_heads(kernels):
    q = jnp.zeros((1, 1, 6, 16, 16))
    for want, takes in ((8, 6), (4, 3), (2, 2), (1, 1)):
        kernels(want)
        assert module._step(q) == {"hb": takes, "interpret": True}


@pytest.mark.parametrize("chunks,heads,shape,write", [
    (1, 8, (1, 3, 16, 24), "mixed"), (3, 2, (1, 3, 16, 24), "mixed"),
    (2, 1, (1, 3, 16, 24), "mixed"),
    # 30 heads, six a step, keys 96 and values 192 wide (neither a lane
    # tile), write strengths over (0, 2)
    (2, 8, (1, 30, 96, 192), "to2")])
def test_the_rule_on_the_kernel_pair_is_the_recurrence(
        kernels, chunks, heads, shape, write):
    """``gated_delta_rule``'s value and five gradients with the kernels
    forming the factors and carrying the state (the inverse, and the
    way back through it, XLA's), weak decay and strong, ``dk`` 16
    beside ``dv`` 24, three heads."""
    kernels(heads)
    if shape[1] == 30:
        assert module._step(jnp.zeros((1, 1, 30, 16, 96)))["hb"] == 6
    # not ``_GRADS``: that jit has traced the rule on the scan
    readings = jax.jit(jax.grad(_weighted(gated_delta_rule),
                                argnums=(0, 1, 2, 3, 4), has_aux=True))
    for decay in (0.01, 5.0):
        operands = _operands(chunks * CHUNK, decay, write, seed=chunks,
                             shape=shape)
        if write == "to2":
            assert 1.5 < float(jnp.max(operands[4])) <= 2.0
        with jax.default_matmul_precision("highest"):
            grads, out = readings(*operands)
            ref = _readings(token_by_token, *operands)
        for name, a, b in zip(NAMES, (out,) + grads, ref):
            err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            assert err < TOL, (name, decay, err)


def test_bf16_operands_keep_a_float32_state_in_the_kernels(kernels):
    operands = _operands(3 * CHUNK, 0.3, "mixed", shape=(1, 4, 32, 16))
    low = tuple(x.astype(jnp.bfloat16) for x in operands[:3]) \
        + operands[3:5]
    on_scan = jax.jit(gated_delta_rule)(*low)
    kernels(2)
    got = jax.jit(gated_delta_rule)(*low)
    assert got.dtype == jnp.bfloat16
    # a bf16 state would differ from the scan's float32 one by 2^-8
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - on_scan.astype(jnp.float32)))) == 0.0
    ref = token_by_token(*(x.astype(jnp.float32) for x in low))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                / jnp.max(jnp.abs(ref)))
    assert err < 2e-2, err


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    kernels(2)
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    operands = _chunk_operands(1, 1, 2, 16, 16, 16, jnp.float32)[:6]
    with pytest.raises(RuntimeError, match="interpret mode"):
        module._rule_of_chunks(*operands, module._decay(operands[3]))
