"""The Gated DeltaNet mixer's elementwise chain as kernel pairs
(``horovod_tpu/ops/gdn_chain.py``), in pallas interpret mode on the CPU,
against the expressions of ``models/llama.py`` that run off the TPU:
outputs and every gradient, the taps' and the gain's among them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama
from horovod_tpu.ops import gated_delta_rule
from horovod_tpu.ops import gdn_chain as module

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def kernels(monkeypatch):
    """Run the chain on its kernels, a grid step taking so many tokens
    and heads, so many tokens a pass."""
    def switch(tokens=8, heads=2, a_pass=4):
        monkeypatch.setattr(module, "_INTERPRET", True)
        monkeypatch.setattr(module, "TOKENS_A_STEP", tokens)
        monkeypatch.setattr(module, "HEADS_A_STEP", heads)
        monkeypatch.setattr(module, "TOKENS_A_PASS", a_pass)
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


def _stage_one(dtype, B, T, hk, hv, d, taps=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkvz = jax.random.normal(ks[0], (B, T, (2 * hk + 2 * hv) * d), F32)
    w = 0.5 * jax.random.normal(ks[1], (taps, (2 * hk + hv) * d), F32)
    weights = [jax.random.normal(k, (B, T, h, d), F32)
               for k, h in zip(ks[2:], (hk, hk, hv, hv))]
    return qkvz.astype(dtype), w.astype(dtype), weights


def _by_heads(outs, d):
    """The kernels' ``[B, T, heads * d]`` as the expression's ``[B, T,
    heads, d]``."""
    return tuple(a.reshape(*a.shape[:2], -1, d) for a in outs)


def _weighted(outs, weights):
    return sum(jnp.sum(o.astype(F32) * w) for o, w in zip(outs, weights))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T, tokens, a_pass", [
    (8, 8, 4),      # one tile: the first tokens see zeros
    (32, 8, 4),     # several: a tile's first tokens see the tile before
    (24, 12, 4),    # a halo of 3 = taps - 1 divides the tile of 12 ...
    (20, 10, 5),    # ... of 10 only a halo of 5 does
    (16, 8, 2),     # a pass shorter than the halo
])
def test_stage_one_is_the_expression(kernels, dtype, T, tokens, a_pass):
    """``hvd_gdn_chain_in_fwd`` / ``_bwd``: ``q``, ``k``, ``v``, ``z``
    and the gradients of ``qkvz`` and of the taps, with fewer key heads
    than value heads and two blocks of heads."""
    B, hk, hv, d = 2, 4, 8, 16
    kernels(tokens, 2, a_pass)
    qkvz, w, weights = _stage_one(dtype, B, T, hk, hv, d)

    def ref(qkvz, w):
        return llama._gdn_chain_in(qkvz, w, hk, hv, d, d)

    def got(qkvz, w):
        return _by_heads(module.chain_in(qkvz, w, hk, hv), d)

    def readings(f):
        """One compiled program a side: the four outputs and the two
        gradients (evaluated eagerly the expression is a compile a
        primitive; the kernel's forward under ``jax.grad`` is the call
        it makes alone)."""
        def loss(*x):
            outs = f(*x)
            return _weighted(outs, weights), outs
        (_, outs), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(qkvz, w)
        return outs, grads

    (outs, grads), (ref_outs, ref_grads) = readings(got), readings(ref)
    for name, a, b in zip("qkvz", outs, ref_outs):
        assert a.dtype == dtype
        _close(a, b, dtype, name)
    for name, a, b in zip(("d qkvz", "d taps"), grads, ref_grads):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


def test_stage_one_with_as_many_key_heads_as_value_heads(kernels):
    kernels(8, 2, 4)
    qkvz, w, weights = _stage_one(F32, 1, 16, 2, 2, 16, taps=3)
    for f in (lambda *x: _by_heads(module.chain_in(*x, 2, 2), 16),
              lambda *x: llama._gdn_chain_in(*x, 2, 2, 16, 16)):
        weights.append(jax.jit(jax.grad(
            lambda *x: _weighted(f(*x), weights[:4]), (0, 1)))(qkvz, w))
    for name, a, b in zip(("d qkvz", "d taps"), *weights[4:]):
        _close(a, b, F32, name)


def test_value_heads_that_are_no_multiple_of_the_key_heads_are_refused():
    qkvz, w, _ = _stage_one(F32, 1, 8, 2, 3, 16)
    with pytest.raises(ValueError, match="no multiple"):
        module.chain_in(qkvz, w, 2, 3)


def test_taps_that_reach_past_a_tile_are_refused(kernels):
    kernels(2, 2, 2)
    qkvz, w, _ = _stage_one(F32, 1, 8, 2, 2, 16)
    with pytest.raises(ValueError, match="reach further back"):
        module.chain_in(qkvz, w, 2, 2)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T, tokens, heads", [(8, 8, 4), (32, 8, 2),
                                              (24, 12, 3)])
def test_stage_two_is_the_expression(kernels, dtype, T, tokens, heads):
    """``hvd_gdn_chain_out_fwd`` / ``_bwd``: the gated norm and the
    gradients of ``o``, ``z`` and the gain."""
    B, H, d, eps = 2, 6 if heads == 3 else 4, 16, 1e-6
    kernels(tokens, heads, 4)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    o, z, weight = (jax.random.normal(k, (B, T, H, d), F32) for k in ks[:3])
    o, z = (3.0 * o).astype(dtype), z.astype(dtype)
    gain = (1.0 + 0.3 * jax.random.normal(ks[3], (d,), F32)).astype(dtype)

    def loss(f):
        return lambda *x: jnp.sum(f(*x, eps).astype(F32) * weight)

    def got(o, z, gain, eps):
        flat = (B, T, H * d)
        return module.chain_out(o.reshape(flat), z.reshape(flat), gain,
                                eps).reshape(o.shape)

    ref = llama._gdn_chain_out
    out = jax.jit(got, static_argnums=3)(o, z, gain, eps)
    assert out.dtype == dtype
    _close(out, jax.jit(ref, static_argnums=3)(o, z, gain, eps), dtype,
           "out")
    grads = [jax.jit(jax.grad(loss(f), (0, 1, 2)))(o, z, gain)
             for f in (got, ref)]
    for name, a, b in zip(("d o", "d z", "d gain"), *grads):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


def _mixer(dtype):
    """One ``linear_attention`` layer's leaves and an input: two key
    heads serving four value heads, 16 wide, four taps."""
    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_head=16, d_ff=64, norm_eps=1e-6, conv_taps=4,
        layer_types=("linear_attention", "full_attention"),
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
        linear_value_dim=16, dtype=dtype, param_dtype=dtype, remat="attn")
    params = llama.llama_init(cfg, jax.random.PRNGKey(0))
    lp = {name: w[0] for name, w in params["linear_layers"].items()
          if name.startswith("gdn_")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32), F32)
    return cfg, lp, x.astype(dtype)


def _mixer_readings(cfg, lp, x):
    """(the mixer's output, the gradients of its input and of its
    leaves) under a checkpoint a stage, as remat "attn/ffn" wraps it."""
    def loss(x, lp):
        out = llama._gated_delta_net(x, lp, cfg, None, None, jax.checkpoint)
        return jnp.sum(out.astype(F32) ** 2), out

    (_, out), (dx, dlp) = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(x, lp)
    return {"out": out, "d x": dx, **dlp}


def _l2(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return np.linalg.norm(got - ref) / np.linalg.norm(ref)


def test_the_mixer_is_the_same_on_both_carriers(kernels, monkeypatch):
    """``_gated_delta_net`` whole in float32, values and the gradients
    of its input and of every leaf it reads: the expressions and the
    scan, then the chain's kernels and the rule's."""
    cfg, lp, x = _mixer("float32")
    ref = _mixer_readings(cfg, lp, x)
    kernels(16, 2, 8)
    monkeypatch.setattr(gated_delta_rule, "_INTERPRET", True)
    got = _mixer_readings(cfg, lp, x)
    for name in ref:
        _close(got[name], ref[name], F32, name)


def test_the_mixer_in_bfloat16_is_no_further_from_float32(
        kernels, monkeypatch):
    """In bfloat16 the two carriers round at different instants (the
    kernels sum the taps' transpose once, in float32) and a layer's
    gradients differ by several percent between them, so each is held
    to what float32 says of the same rounded inputs: the kernels stand
    no further from it than the expressions do."""
    cfg, lp, x = _mixer("bfloat16")
    up = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)  # noqa: E731
    exact = _mixer_readings(_mixer("float32")[0], up(lp), up(x))
    ref = _mixer_readings(cfg, lp, x)
    kernels(16, 2, 8)
    monkeypatch.setattr(gated_delta_rule, "_INTERPRET", True)
    got = _mixer_readings(cfg, lp, x)
    for name in ref:
        assert got[name].dtype == jnp.bfloat16
        mine, theirs = (_l2(a[name], exact[name]) for a in (got, ref))
        assert mine < 1.25 * theirs + 2e-3, (name, mine, theirs)


def test_keys_and_values_of_two_widths_take_the_expression(kernels):
    kernels()
    x = jnp.zeros((1, 8, 8))
    assert module.on_kernels(x, 16, 16) and not module.on_kernels(x, 8, 16)


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    kernels()
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        module.on_kernels(jnp.zeros((1, 8, 8)), 16, 16)
