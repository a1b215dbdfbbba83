"""The selective scan (``ops/selective_scan.py``) against the recurrence
token by token as it is written: ``y`` and all six gradients, for a
sequence shorter than a chunk, a whole number of chunks and one that is
no multiple of the chunk (padded with tokens that neither decay nor
write), small and large steps, float32 and bf16 operands.

Two carriers: the ``lax.scan`` over chunks of a ``lax.scan`` over tokens
(the CPU's) and the Pallas kernel pair (a TPU's), which runs here in
interpret mode. Float32: the carriers and the recurrence differ in the
order of float32 additions only, 2e-5 of the largest entry. bf16
operands are read as float32 by all three; what differs is ``y``,
``du``, ``dB`` and ``dC`` rounded to bf16 as they leave (2^-9 of their
own size each, against the largest entry after the gradients' sums):
1e-2. Small sizes, a case compiled once."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import selective_scan as module
from horovod_tpu.ops.selective_scan import CHUNK, selective_scan

pytestmark = pytest.mark.quick
F32 = jnp.float32
NAMES = ("y", "du", "ddt", "dA", "dB", "dC", "dD")
TOL = {"float32": 2e-5, "bfloat16": 1e-2}


def token_by_token(u, dt, A, Bm, Cm, D):
    """``s <- exp(dt A) s + dt u B; y = s . C + D u`` from a zero state,
    one token after another."""
    def token(s, x):
        u, dt, Bt, Ct = x
        s = jnp.exp(dt[..., None] * A) * s \
            + (dt * u)[..., None] * Bt[:, None, :]
        return s, jnp.sum(s * Ct[:, None, :], -1) + D * u

    b, _, c = u.shape
    _, y = jax.lax.scan(
        token, jnp.zeros((b, c, A.shape[1]), F32),
        tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def _operands(t, dtype, b=2, c=256, n=16, seed=0):
    """``u`` after a SiLU, steps from 1e-3 to about 1 (a channel's bias
    log-uniform, as the model starts), ``A[c, n]`` near ``-(n + 1)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    start = jnp.exp(jax.random.uniform(ks[0], (c,), F32, jnp.log(1e-3),
                                       jnp.log(0.3)))
    return (jax.nn.silu(jax.random.normal(ks[1], (b, t, c))).astype(dtype),
            jax.nn.softplus(start + jnp.log(-jnp.expm1(-start))
                            + jax.random.normal(ks[2], (b, t, c))),
            -jnp.arange(1, n + 1, dtype=F32) * jnp.exp(
                0.3 * jax.random.normal(ks[3], (c, 1))),
            jax.random.normal(ks[4], (b, t, n)).astype(dtype),
            jax.random.normal(ks[5], (b, t, n)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(ks[6], (c,)),
            jax.random.normal(ks[7], (b, t, c)))


def _readings(scan):
    """-> jitted (y, the six gradients of ``sum(y * w)``), float32."""
    def loss(u, dt, A, Bm, Cm, D, w):
        y = scan(u, dt, A, Bm, Cm, D).astype(F32)
        return jnp.sum(y * w), y

    def run(*operands):
        grads, y = jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5),
                            has_aux=True)(*operands)
        return tuple(x.astype(F32) for x in (y,) + grads)
    return jax.jit(run)


def _errs(got, ref):
    return {name: float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
            for name, g, r in zip(NAMES, got, ref)}


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setattr(module, "_INTERPRET", True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [40, 2 * CHUNK, 2 * CHUNK + 44])
def test_the_scan_over_chunks_is_the_recurrence(t, dtype):
    operands = _operands(t, jnp.dtype(dtype))
    err = _errs(_readings(selective_scan)(*operands),
                _readings(token_by_token)(*operands))
    assert max(err.values()) < TOL[dtype], err


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [40, 2 * CHUNK, 2 * CHUNK + 44])
def test_the_kernel_pair_is_the_recurrence(kernels, t, dtype):
    operands = _operands(t, jnp.dtype(dtype), seed=1)
    got = _readings(selective_scan)(*operands)
    assert got[0].shape == operands[0].shape
    err = _errs(got, _readings(token_by_token)(*operands))
    assert max(err.values()) < TOL[dtype], err


def test_the_kernels_walk_several_blocks_of_channels(kernels, monkeypatch):
    """Three blocks of 128 channels of 384: a block's state and its
    cotangent wait in the scratch while the others' chunks run, and a
    token's ``dB`` and ``dC`` are summed over the blocks."""
    monkeypatch.setattr(module, "CHANNELS_A_STEP", 128)
    operands = _operands(CHUNK + 24, F32, b=1, c=384, n=8, seed=2)
    assert module._step(operands[0])["cb"] == 128
    err = _errs(_readings(selective_scan)(*operands),
                _readings(token_by_token)(*operands))
    assert max(err.values()) < TOL["float32"], err


def test_channels_off_the_lanes_take_the_scan(kernels):
    """96 channels are no multiple of the 128 lanes: the plain form runs
    whatever the device (and is right)."""
    operands = _operands(48, F32, c=96, seed=3)
    text = jax.jit(selective_scan).lower(*operands[:6]).as_text()
    assert "hvd_ssm_scan" not in text
    err = _errs(_readings(selective_scan)(*operands),
                _readings(token_by_token)(*operands))
    assert max(err.values()) < TOL["float32"], err


def test_a_strong_decay_forgets_and_a_zero_step_keeps():
    """``dt A`` of -11 a token underflows no product (every exponential
    has a non-positive argument), and tokens of ``dt`` = 0, what a
    sequence is padded with, neither decay nor write: every later token
    reads the state the last real one left."""
    u, dt, A, Bm, Cm, D, _ = _operands(64, F32, b=1, c=128, seed=4)
    y = jax.jit(selective_scan)(u, jnp.full_like(dt, 11.0),
                                -jnp.ones_like(A), Bm, Cm, D)
    assert bool(jnp.all(jnp.isfinite(y)))
    held = jnp.concatenate([dt[:, :32], jnp.zeros_like(dt[:, :32])], 1)
    same_c = jnp.broadcast_to(Cm[:, 31:32], Cm.shape)
    y = jax.jit(selective_scan)(u, held, A, Bm, same_c, 0.0 * D)
    assert float(jnp.max(jnp.abs(y[:, 32:] - y[:, 31:32]))) == 0.0
    assert float(jnp.max(jnp.abs(y[:, 31]))) > 1e-3
