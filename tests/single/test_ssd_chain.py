"""The Mamba-2 mixer's elementwise chain as kernel pairs
(``horovod_tpu/ops/ssd_chain.py``), in pallas interpret mode on the CPU,
against the expressions of ``models/llama.py`` that run off the TPU:
outputs and every gradient, the taps', the bias's and the gain's among
them. Small sizes: a lane slab is 8 lanes here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama
from horovod_tpu.ops import ssd
from horovod_tpu.ops import ssd_chain as module

pytestmark = pytest.mark.quick
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def kernels(monkeypatch):
    """Run the chain on its kernels, a grid step taking so many tokens
    and lanes, so many tokens a pass, a lane slab so wide."""
    def switch(tokens=8, lanes=16, a_pass=4, slab=8):
        monkeypatch.setattr(module, "_INTERPRET", True)
        monkeypatch.setattr(module, "TOKENS_A_STEP", tokens)
        monkeypatch.setattr(module, "LANES_A_STEP", lanes)
        monkeypatch.setattr(module, "TOKENS_A_PASS", a_pass)
        monkeypatch.setattr(module, "LANES", slab)
    return switch


def _close(got, ref, dtype, what):
    """To rounding: float32 to its last digits; bfloat16 to a few of its
    ulps at the largest value (the kernels round where the expression
    rounds, but sum the taps' transpose once and in float32)."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (what, got.shape, ref.shape)
    scale = np.max(np.abs(ref)) + 1e-30
    err = np.max(np.abs(got - ref)) / scale
    assert err < (2e-5 if dtype == F32 else 2.5e-2), (what, err)


def _weighted(outs, weights):
    return sum(jnp.sum(o.astype(F32) * w) for o, w in zip(outs, weights))


def _stage_one(dtype, B, T, di, gn, H, bias, taps=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    zxr = jax.random.normal(ks[0], (B, T, 2 * di + 2 * gn + H), F32)
    w = 0.5 * jax.random.normal(ks[1], (taps, di + 2 * gn), F32)
    b = 0.3 * jax.random.normal(ks[2], (di + 2 * gn,), F32) if bias else None
    weights = [jax.random.normal(k, (B, T, n), F32)
               for k, n in zip(ks[3:], (di, gn, gn, di, H))]
    return zxr.astype(dtype), w.astype(dtype), \
        None if b is None else b.astype(dtype), weights


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T, tokens, a_pass, di, gn, H, bias", [
    (8, 8, 4, 32, 16, 8, True),      # one tile: the first tokens see zeros
    (32, 8, 4, 32, 16, 8, True),     # several: a tile's sees the tile before
    (24, 12, 4, 32, 16, 4, False),   # a halo of 3 = taps - 1; no bias; an
                                     # r narrower than a block
    (20, 10, 5, 64, 8, 12, True),    # a halo of 5; B and C one block; r
                                     # of one block and a half
    (16, 8, 2, 48, 16, 16, False),   # a pass shorter than the halo
], ids=["one-tile", "tiles", "halo3-nobias", "halo5-wide-r", "short-pass"])
def test_stage_one_is_the_expression(kernels, dtype, T, tokens, a_pass, di,
                                     gn, H, bias):
    """``hvd_ssd_chain_in_fwd`` / ``_bwd``: ``X``, ``B``, ``C``, ``z``,
    ``r`` and the gradients of ``zxr``, of the taps and of their
    bias."""
    kernels(tokens, 16, a_pass)
    zxr, w, b, weights = _stage_one(dtype, 2, T, di, gn, H, bias)
    assert module.on_kernels(zxr, di, 1, gn)
    over = (0, 1, 2) if bias else (0, 1)

    def ref(zxr, w, b=None):
        return llama._ssd_chain_in(zxr, w, b, di, gn)

    def got(zxr, w, b=None):
        return module.chain_in(zxr, w, b, di, gn)

    args = (zxr, w, b)[:len(over)]
    def readings(f):
        def loss(*x):
            outs = f(*x)
            return _weighted(outs, weights), outs
        return jax.jit(jax.grad(loss, over, has_aux=True))(*args)[::-1]

    (outs, grads), (ref_outs, ref_grads) = readings(got), readings(ref)
    names = "XBCzr", ("d zxr", "d taps", "d bias")
    for name, x, y in (*zip(names[0], outs, ref_outs),
                       *zip(names[1], grads, ref_grads)):
        assert x.dtype == dtype
        _close(x, y, dtype, name)


def test_taps_that_reach_past_a_tile_are_refused(kernels):
    kernels(2, 16, 2)
    zxr, w, b, _ = _stage_one(F32, 1, 8, 32, 16, 8, True)
    with pytest.raises(ValueError, match="reach further back"):
        module.chain_in(zxr, w, b, 32, 16)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T, tokens, G, d", [
    (8, 8, 1, 8),       # one tile; one group of one slab
    (32, 8, 4, 8),      # several tiles; two groups a step
    (24, 12, 2, 24),    # a group of three slabs, wider than a step
    (16, 8, 3, 16),     # three groups of two slabs, one a step
], ids=["G1-slab", "G4-slab", "G2-3slabs", "G3-2slabs"])
def test_stage_two_is_the_expression(kernels, dtype, T, tokens, G, d):
    """``hvd_ssd_chain_out_fwd`` / ``_bwd``: the gate, the norm a group
    and the gradients of ``y``, ``z`` and the gain."""
    B, eps = 2, 1e-5
    kernels(tokens, 16, 4)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    y, z, weight = (jax.random.normal(k, (B, T, G * d), F32) for k in ks[:3])
    y, z = (3.0 * y).astype(dtype), z.astype(dtype)
    gain = (1.0 + 0.3 * jax.random.normal(ks[3], (G * d,), F32)
            ).astype(dtype)
    assert module.on_kernels(y, G * d, G, 8)

    def readings(f):
        def loss(*x):
            out = f(*x, G, eps)
            return jnp.sum(out.astype(F32) * weight), out
        grads, out = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(
            y, z, gain)
        return (out,) + grads

    for name, a, b in zip(("out", "d y", "d z", "d gain"),
                          readings(module.chain_out),
                          readings(llama._ssd_chain_out)):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


def _mixer(dtype):
    """One ``mamba2`` layer's leaves and an input: eight heads of 16
    channels in two groups of 32 states, four taps and their bias."""
    cfg = llama.LlamaConfig.tiny(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_head=16, d_ff=64, norm_eps=1e-5, conv_taps=4,
        mamba_conv_bias=True, one_part_layers=True,
        layer_types=("mamba2", "full_attention"), ssd_heads=8,
        ssd_head_dim=16, ssd_state=32, ssd_groups=2, ssd_chunk=16,
        dtype=dtype, param_dtype=dtype, remat="attn")
    params = llama.llama_init(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 16))
    lp = {name: w[0] + (0.1 * jax.random.normal(next(keys), w[0].shape)
                        ).astype(w.dtype)
          for name, w in params["mamba2_layers"].items()
          if name.startswith("ssd_")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), F32)
    return cfg, lp, x.astype(dtype)


def _mixer_readings(cfg, lp, x):
    """(the mixer's output, the gradients of its input and of its
    leaves) under the layer's checkpoint, as remat "attn" wraps it."""
    def loss(x, lp):
        out = jax.checkpoint(
            lambda x, lp: llama._mamba2(x, lp, cfg, None, None))(x, lp)
        return jnp.sum(out.astype(F32) ** 2), out

    (_, out), (dx, dlp) = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(x, lp)
    return {"out": out, "d x": dx, **dlp}


def _l2(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return np.linalg.norm(got - ref) / np.linalg.norm(ref)


def test_the_mixer_is_the_same_on_both_carriers(kernels, monkeypatch):
    """``_mamba2`` whole in float32, values and the gradients of its
    input and of every leaf it reads: the expressions and the scan, then
    the chain's kernels and the recurrence's."""
    cfg, lp, x = _mixer("float32")
    ref = _mixer_readings(cfg, lp, x)
    kernels(16, 32, 8)
    monkeypatch.setattr(ssd, "_INTERPRET", True)
    got = _mixer_readings(cfg, lp, x)
    assert set(got) == set(ref) and "ssd_conv_bias" in got
    for name in ref:
        assert _l2(got[name], ref[name]) < 2e-4, name


def test_the_mixer_in_bfloat16_is_no_further_from_float32(
        kernels, monkeypatch):
    """In bfloat16 the two carriers round at different instants (the
    kernels sum the taps' transpose once, in float32), so each is held
    to what float32 says of the same rounded inputs: the kernels stand
    no further from it than the expressions do."""
    cfg, lp, x = _mixer("bfloat16")
    up = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)  # noqa: E731
    exact = _mixer_readings(_mixer("float32")[0], up(lp), up(x))
    ref = _mixer_readings(cfg, lp, x)
    kernels(16, 32, 8)
    monkeypatch.setattr(ssd, "_INTERPRET", True)
    got = _mixer_readings(cfg, lp, x)
    for name in ref:
        assert got[name].dtype == jnp.bfloat16
        mine, theirs = (_l2(a[name], exact[name]) for a in (got, ref))
        assert mine < 1.25 * theirs + 2e-3, (name, mine, theirs)


@pytest.mark.parametrize("di, G, gn, why", [
    (64, 2, 12, "B and C of a slab and a half"),
    (36, 1, 16, "channels that are no whole slabs"),
    (32, 8, 16, "a group narrower than a slab"),
    (24, 1, 16, "B's window starts inside a block"),
])
def test_columns_the_kernels_cannot_tile_take_the_expression(
        kernels, di, G, gn, why):
    kernels()
    x = jnp.zeros((1, 8, 8))
    assert module.on_kernels(x, 64, 2, 16)
    assert not module.on_kernels(x, di, G, gn), why


def test_off_the_tpu_the_expression_runs():
    assert not module.on_kernels(jnp.zeros((1, 8, 8)), 8192, 8, 1024)


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    kernels()
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        module.on_kernels(jnp.zeros((1, 8, 8)), 64, 2, 16)
