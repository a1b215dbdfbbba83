"""The SSD recurrence of a Mamba-2 layer (``ops/ssd.py``) against the
recurrence token by token as it is written: ``y`` and all six gradients,
one chunk and several, heads that share ``B`` / ``C`` by groups, small
and large steps, float32 and bf16 operands; a sequence that is no
multiple of a chunk and heads that are no multiple of the groups are
refused by name.

Two carriers: the ``lax.scan`` over chunks of the chunked form (the
CPU's) and the Pallas kernel pair (a TPU's), which runs here in
interpret mode. Float32: the carriers and the recurrence differ in the
order of float32 additions and in ``exp`` of a difference against a
product of ``exp``s (``dA`` sums both over every token: 7e-5), 2e-4 of
the largest entry. bf16 operands: the
chunked form rounds the decayed scores, ``dt x`` and the state to bf16 as
they enter a matmul (and ``y``, ``dx``, ``dB``, ``dC`` as they leave):
2e-2. Small sizes, a case compiled once."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models.reference import nemotronh_ssd
from horovod_tpu.ops import ssd as module
from horovod_tpu.ops.ssd import ssd

pytestmark = pytest.mark.quick
F32 = jnp.float32
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
TOL = {"float32": 2e-4, "bfloat16": 2e-2}


def token_by_token(x, dt, A, Bm, Cm, D, chunk=None):
    """``S <- exp(dt A) S + dt x (x) B; y = S C + D x`` from a zero
    state, one token after another: the program's reference
    (``models/reference.py``), operands read as float32."""
    return nemotronh_ssd(*(a.astype(F32) for a in (x, dt, A, Bm, Cm, D)))


def _operands(t, dtype, b=2, h=4, p=64, g=2, n=128, seed=0):
    """``x``, ``B``, ``C`` after a SiLU, steps from 1e-3 to about 1 (a
    head's bias log-uniform, as the model starts), ``A`` in -(1, 16)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    start = jnp.exp(jax.random.uniform(ks[0], (h,), F32, jnp.log(1e-3),
                                       jnp.log(0.3)))

    def silu(k, shape, scale=1.0):
        return (jax.nn.silu(jax.random.normal(k, shape)) * scale
                ).astype(dtype)

    return (silu(ks[1], (b, t, h, p)),
            jax.nn.softplus(start + jnp.log(-jnp.expm1(-start))
                            + jax.random.normal(ks[2], (b, t, h))),
            -jax.random.uniform(ks[3], (h,), F32, 1.0, 16.0),
            silu(ks[4], (b, t, g, n), n ** -0.25),
            silu(ks[5], (b, t, g, n), n ** -0.25),
            1.0 + 0.1 * jax.random.normal(ks[6], (h,)),
            jax.random.normal(ks[7], (b, t, h, p)))


def _readings(rule, chunk):
    """-> jitted (y, the six gradients of ``sum(y * w)``), float32."""
    def loss(x, dt, A, Bm, Cm, D, w):
        y = rule(x, dt, A, Bm, Cm, D, chunk).astype(F32)
        return jnp.sum(y * w), y

    def run(*operands):
        grads, y = jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5),
                            has_aux=True)(*operands)
        return tuple(a.astype(F32) for a in (y,) + grads)
    return jax.jit(run)


def _errs(got, ref):
    return {name: float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
            for name, g, r in zip(NAMES, got, ref)}


@pytest.fixture
def kernels(monkeypatch):
    """The kernel pair in interpret mode, and nothing else: the scan
    form is taken away."""
    def no_scan(*_):
        raise AssertionError("the scan form ran under _INTERPRET")

    monkeypatch.setattr(module, "_INTERPRET", True)
    monkeypatch.setattr(module, "_scan_core", no_scan)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t, chunk", [(64, 64), (192, 64), (256, 128)])
def test_the_scan_over_chunks_is_the_recurrence(t, chunk, dtype):
    operands = _operands(t, jnp.dtype(dtype))
    err = _errs(_readings(ssd, chunk)(*operands),
                _readings(token_by_token, None)(*operands))
    assert max(err.values()) < TOL[dtype], err


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t, chunk", [(128, 128), (256, 128)])
def test_the_kernel_pair_is_the_recurrence(kernels, t, chunk, dtype):
    operands = _operands(t, jnp.dtype(dtype), seed=1)
    got = _readings(ssd, chunk)(*operands)
    assert got[0].shape == operands[0].shape
    err = _errs(got, _readings(token_by_token, None)(*operands))
    assert max(err.values()) < TOL[dtype], err


@pytest.mark.parametrize("h, g, heads_a_step, hb", [
    (8, 2, 16, 4),       # a group's four heads a step: two slabs of two
    (8, 1, 2, 2),        # a group's eight heads over four steps: dB, dC
    (3, 3, 16, 1)])      # summed outside; a head alone: half a slab
def test_the_kernels_by_groups_of_heads(kernels, monkeypatch, h, g,
                                        heads_a_step, hb):
    """Heads that share ``B`` / ``C``: a step takes a group's heads (or
    a divisor of them), two heads of 64 channels a 128-lane slab, and a
    group's ``dB`` / ``dC`` are the sum over its heads, in the step and
    over the steps."""
    monkeypatch.setattr(module, "HEADS_A_STEP", heads_a_step)
    operands = _operands(256, F32, b=1, h=h, g=g, seed=2)
    assert module._step(operands[0], operands[3], 128)["hb"] == hb
    err = _errs(_readings(ssd, 128)(*operands),
                _readings(token_by_token, None)(*operands))
    assert max(err.values()) < TOL["float32"], err


def test_heads_as_wide_as_the_lanes(kernels):
    """``P`` = 128: a head is a slab, nothing is chosen by lane."""
    operands = _operands(128, F32, b=1, h=2, p=128, g=1, n=64, seed=3)
    err = _errs(_readings(ssd, 64)(*operands),
                _readings(token_by_token, None)(*operands))
    assert max(err.values()) < TOL["float32"], err


def test_a_head_a_group_a_unit_step_and_no_skip(kernels):
    """Lightning Attention's shapes: ``P = N = 128``, a group a head
    (``hb`` = 1: a step's ``dB`` / ``dC`` are the group's and leave in
    the operands' dtype), ``dt = 1`` and no ``D``: the kernel pair is the
    recurrence, and ``D = None`` is ``D = 0`` without its pass."""
    x, _, A, Bm, Cm, _, w = _operands(256, F32, b=1, h=2, p=128, g=2,
                                      n=128, seed=4)
    one, none = jnp.ones(x.shape[:3], F32), jnp.zeros((2,), F32)
    Cm = Cm / 128 ** 0.5
    got = _readings(lambda x, dt, A, Bm, Cm, D, chunk: ssd(
        x, dt, A, Bm, Cm, None, chunk), 128)(x, one, A / 16, Bm, Cm, none, w)
    ref = _readings(token_by_token, None)(x, one, A / 16, Bm, Cm, none, w)
    err = _errs(got[:6], ref[:6])
    assert max(err.values()) < TOL["float32"], err
    assert float(jnp.abs(got[6]).max()) == 0.0       # no D, no gradient


@pytest.mark.parametrize("case, match", [
    (dict(t=100), "no multiple"),
    (dict(t=128, h=3, g=2), "groups")])
def test_what_a_step_cannot_take_is_refused_by_name(case, match):
    operands = _operands(**{"dtype": F32, **case})[:6]
    with pytest.raises(ValueError, match=match):
        ssd(*operands, 64)


def test_a_strong_decay_forgets_and_a_zero_step_keeps():
    """``dt A`` of -176 a token underflows no product (every exponential
    has a non-positive argument), and tokens of ``dt`` = 0, what a
    sequence is padded with, neither decay nor write: every later token
    reads the state the last real one left."""
    x, dt, A, Bm, Cm, D, _ = _operands(128, F32, b=1, seed=4)
    y = jax.jit(lambda *a: ssd(*a, 64))(
        x, jnp.full_like(dt, 11.0), jnp.full_like(A, -16.0), Bm, Cm, D)
    assert bool(jnp.all(jnp.isfinite(y)))
    held = jnp.concatenate([dt[:, :64], jnp.zeros_like(dt[:, :64])], 1)
    same_c = jnp.broadcast_to(Cm[:, 63:64], Cm.shape)
    y = jax.jit(lambda *a: ssd(*a, 64))(x, held, A, Bm, same_c, 0.0 * D)
    assert float(jnp.max(jnp.abs(y[:, 64:] - y[:, 63:64]))) < 1e-6
    assert float(jnp.max(jnp.abs(y[:, 63]))) > 1e-3
