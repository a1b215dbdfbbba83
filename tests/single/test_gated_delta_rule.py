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
        assert module._chunk_major_step(q.shape[2]) == takes


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
        assert module._chunk_major_step(30) == 6
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


# ---------------------------------------------------------------------
# Token-major operands: ``q``, ``k`` [B, T, hk dk], ``v``, ``o`` [B, T,
# hv dv], a head a lane slice of a block, a key head indexed.
# ---------------------------------------------------------------------

WIDE = 128      # a lane tile: the narrowest head with a token-major step


def _token_major(dtype, hk, hv, t=2 * CHUNK, d=WIDE, seed=0, dv=None):
    """``_operands`` at ``hk`` key heads, rounded to ``dtype``, as the
    chain hands them over: (``q``, ``k`` [1, T, hk d], ``v`` [1, T, hv
    dv], ``g``, ``beta`` [1, T, hv] float32, a weight for ``o``)."""
    dv = dv or d
    q, k, _, _, _, _ = _operands(t, 0.3, "mixed", seed, (1, hk, d, dv))
    _, _, v, g, beta, w = _operands(t, 0.3, "mixed", seed, (1, hv, d, dv))
    return tuple(x.astype(dtype).reshape(1, t, -1)
                 for x in (q, k, v)) + (g, beta, w.astype(dtype).reshape(
                     1, t, -1))


def _token_major_readings(hk, *operands):
    """(``o``, the five gradients of ``sum(o * w)``): a jit a call, so
    that each traces the carrier the module stands on."""
    def f(q, k, v, g, beta, w):
        o = gated_delta_rule(q, k, v, g, beta, key_heads=hk)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    grads, o = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4),
                                has_aux=True))(*operands)
    return (o,) + grads


@pytest.fixture
def blocks_seen(monkeypatch):
    """-> a list that every ``_kernel_fwd`` from then on appends its
    ``q``'s rank (3 token-major, 5 chunk-major) and heads a step to."""
    seen, kernel_fwd = [], module._kernel_fwd
    monkeypatch.setattr(module, "_kernel_fwd", lambda *a, **kw: (
        seen.append((a[0].ndim, kw["hb"])), kernel_fwd(*a, **kw))[1])
    return seen


def _by_value_heads(operands, hk, hv):
    """Token-major operands as the recurrence takes them: float32 by
    heads, a key head REPEATED for the value heads it serves."""
    q, k, v, g, beta, w = (x.astype(jnp.float32) for x in operands)
    b, t, _ = q.shape
    q, k = (jnp.repeat(x.reshape(b, t, hk, -1), hv // hk, axis=2)
            for x in (q, k))
    return q, k, v.reshape(b, t, hv, -1), g, beta, w.reshape(b, t, hv, -1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hk,hv,heads", [(2, 2, 2), (2, 4, 2), (1, 2, 8)],
                         ids=["a-key-head-a-value-head", "one-serves-two",
                              "one-serves-two-in-one-step"])
def test_the_token_major_pair_is_the_scan_and_the_recurrence(
        kernels, dtype, hk, hv, heads):
    """The kernel pair on token-major blocks (a chunk's heads lane
    slices of ``[C, hb d]``, a key head read once for its value heads,
    ``dq`` and ``dk`` summed over them in the kernel) against the scan
    on the same operands chunked and repeated by a copy: in float32
    ``o`` to the last bit and the gradients to where float32 sums differ
    in order; in bfloat16 to a step of it (128 products a sum: the CPU's
    batched matmul and the interpreter's add them in another order, and
    a factor now and then rounds the other way; the repeated form rounds
    each value head's share of ``dq``, ``dk`` and then adds). And
    against the recurrence token by token on the repeated operands."""
    operands = _token_major(dtype, hk, hv)
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        ref = _token_major_readings(hk, *operands)
        kernels(heads)
        assert module._token_major_step(hk, hv, WIDE, WIDE) == min(heads, hv)
        got = _token_major_readings(hk, *operands)
        slow = _readings(token_by_token, *_by_value_heads(operands, hk, hv))
    b, t, _ = operands[0].shape
    for name, a, b_, c in zip(NAMES, got, ref, slow):
        assert a.dtype == b_.dtype and a.shape == b_.shape, name
        a, b_ = a.astype(jnp.float32), b_.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(b_)))
        err = float(jnp.max(jnp.abs(a - b_)))
        if dtype == jnp.float32:
            step = 0.0 if name == "out" else 1e-4 if name in (
                "dg", "dbeta") else 2e-6
        else:
            step = 1e-3 if name in ("dg", "dbeta") else 2.0 ** -7
        assert err <= step * scale, (name, err)
        # the recurrence's gradients of the REPEATED q, k: a key head's
        # is the sum over the value heads it serves
        c = c.reshape(b, t, hv, -1)
        if name in ("dq", "dk"):
            c = c.reshape(b, t, hk, hv // hk, -1).sum(3)
        err = float(jnp.max(jnp.abs(a - c.reshape(a.shape)))
                    / jnp.max(jnp.abs(c)))
        assert err < (TOL if dtype == jnp.float32 else 3e-2), (name, err)


@pytest.mark.parametrize("hk,hv,dk,dv", [
    (2, 2, WIDE, WIDE), (1, 2, WIDE, WIDE),
    # no step of whole lane tiles: the whole width, three heads' lane
    # windows of 16 and 24 wherever they fall
    (3, 3, 16, 24)],
    ids=lambda x: str(x))
def test_both_block_layouts_give_the_same_bits(kernels, monkeypatch,
                                               blocks_seen, hk, hv, dk, dv):
    """One set of kernel bodies behind two block layouts: the
    token-major blocks run at a lane tile's width and, all the heads a
    step, at widths with no step of whole tiles; told that neither fits
    (the whole width's state too large), the same operands go
    chunk-major by a copy (a key head repeated) and ``o`` is the same to
    the last bit, the gradients to a step of bfloat16 (``dq``, ``dk``:
    summed before the rounding or after)."""
    operands = _token_major(jnp.bfloat16, hk, hv, d=dk, dv=dv, seed=1)
    kernels(2)
    step = 2 if dk == WIDE else hv
    assert module._token_major_step(hk, hv, dk, dv) == step
    got = _token_major_readings(hk, *operands)
    assert set(blocks_seen) == {(3, step)}
    monkeypatch.setattr(module, "_token_major_step", lambda *a: None)
    ref = _token_major_readings(hk, *operands)
    assert {rank for rank, _ in blocks_seen} == {3, 5}
    for name, a, b in zip(NAMES, got, ref):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)))
        step = 2.0 ** -7 if name in ("dq", "dk") else 2e-6
        assert err <= (0.0 if name in ("out", "dv") else
                       step * float(jnp.max(jnp.abs(b)))), (name, err)


@pytest.mark.parametrize("sizes,heads,step", [
    # whole lane tiles in both strips: the largest such divisor
    ((16, 32, 128, 128), 8, 8), ((2, 4, 64, 128), 8, 4),
    ((64, 64, 96, 192), 8, 8), ((1, 4, 128, 128), 8, 4),
    # none (heads of 96 want steps of four and 30 has no such divisor;
    # an odd number of 64-wide key heads; fewer heads a step than a key
    # head serves): the whole width
    ((30, 30, 96, 192), 8, 30), ((3, 3, 16, 24), 2, 3),
    ((2, 4, 64, 128), 2, 4), ((16, 32, 128, 128), 1, 32),
    # ... but where its state (46 x 96 x 256 float32) is over 4 MiB:
    # the chunk-major blocks
    ((46, 46, 96, 192), 8, None)],
    ids=lambda s: "x".join(map(str, s)) if isinstance(s, tuple) else None)
def test_the_block_layout_is_read_off_the_operands_shapes(
        kernels, sizes, heads, step):
    kernels(heads)
    assert module._token_major_step(*sizes) == step


def test_a_width_with_no_whole_tile_step_takes_the_whole_width(
        kernels, monkeypatch, blocks_seen):
    """Three heads 16 and 24 wide from token-major operands: ONE grid
    step a chunk takes them all, and ``o`` is the scan's bit for bit;
    with the whole width's state ruled too large, the chunk-major
    blocks, two heads a step, and the same bits again."""
    hv, dk, dv = 3, 16, 24
    kernels(2)
    q, k, v, g, beta, _ = _operands(2 * CHUNK, 0.3, "mixed",
                                    shape=(1, hv, dk, dv))
    flat = [x.astype(jnp.bfloat16).reshape(1, 2 * CHUNK, -1)
            for x in (q, k, v)]
    whole = jax.jit(lambda *a: gated_delta_rule(*a))(*flat, g, beta)
    assert blocks_seen == [(3, 3)]
    assert whole.shape == (1, 2 * CHUNK, hv * dv)
    monkeypatch.setattr(module, "_WHOLE_WIDTH_STATE", 0)
    chunked = jax.jit(lambda *a: gated_delta_rule(*a))(*flat, g, beta)
    assert blocks_seen == [(3, 3), (5, 1)]
    monkeypatch.setattr(module, "_INTERPRET", False)
    scan = jax.jit(lambda *a: gated_delta_rule(*a))(*flat, g, beta)
    for got in (whole, chunked):
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - scan.astype(jnp.float32)))) == 0.0


def test_operands_by_heads_are_read_as_token_major():
    """``[B, T, H, d]`` holds the same bytes as ``[B, T, H d]``: the
    rule by heads (the recurrence's layout, and the benchmark's) is the
    rule token-major, reshaped."""
    q, k, v, g, beta, _ = _operands(CHUNK, 0.3, "mixed")
    flat = [x.reshape(2, CHUNK, -1) for x in (q, k, v)]
    by_heads = jax.jit(gated_delta_rule)(q, k, v, g, beta)
    assert by_heads.shape == v.shape
    token_major = jax.jit(gated_delta_rule)(*flat, g, beta)
    assert token_major.shape == flat[2].shape
    assert float(jnp.max(jnp.abs(by_heads.reshape(flat[2].shape)
                                 - token_major))) == 0.0
    # fewer key heads than value heads: ``key_heads`` says how many
    served = jax.jit(lambda *a: gated_delta_rule(*a, key_heads=1))(
        flat[0][..., :16], flat[1][..., :16], flat[2], g, beta)
    ref = token_by_token(*(jnp.repeat(x[:, :, :1], 3, axis=2)
                           for x in (q, k)), v, g, beta)
    assert float(jnp.max(jnp.abs(served.reshape(v.shape) - ref))) < 1e-5
