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


def _stage_one(dtype, B, T, hk, hv, d, taps=4, seed=0, dv=None):
    dv = dv or d
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkvz = jax.random.normal(ks[0], (B, T, 2 * hk * d + 2 * hv * dv), F32)
    w = 0.5 * jax.random.normal(ks[1], (taps, 2 * hk * d + hv * dv), F32)
    weights = [jax.random.normal(k, (B, T, h, width), F32)
               for k, h, width in zip(ks[2:], (hk, hk, hv, hv),
                                      (d, d, dv, dv))]
    return qkvz.astype(dtype), w.astype(dtype), weights


ONE = (2, 4, 8, 16, 16)     # batch, heads and ONE width of keys and values


def _by_heads(outs, d, dv=None):
    """The kernels' ``[B, T, heads * d]`` as the expression's ``[B, T,
    heads, d]``."""
    return tuple(a.reshape(*a.shape[:2], -1, width)
                 for a, width in zip(outs, (d, d, dv or d, dv or d)))


def _weighted(outs, weights):
    return sum(jnp.sum(o.astype(F32) * w) for o, w in zip(outs, weights))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T, tokens, a_pass, sizes", [
    (8, 8, 4, ONE),      # one tile: the first tokens see zeros
    (32, 8, 4, ONE),     # several: a tile's first tokens see the tile before
    (24, 12, 4, ONE),    # a halo of 3 = taps - 1 divides the tile of 12 ...
    (20, 10, 5, ONE),    # ... of 10 only a halo of 5 does
    (16, 8, 2, ONE),     # a pass shorter than the halo
    # keys 96 and values 192 wide, 30 heads of each (the strip: ``q``
    # ends in the middle of a lane tile and of a step's 1152 lanes)
    (32, 16, 8, (1, 30, 30, 96, 192)),
    # ... a lane group a step, one key head serving two value heads
    (16, 8, 8, (2, 2, 4, 96, 192)),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else None)
def test_stage_one_is_the_expression(kernels, dtype, T, tokens, a_pass,
                                     sizes):
    """``hvd_gdn_chain_in_fwd`` / ``_bwd``: ``q``, ``k``, ``v``, ``z``
    and the gradients of ``qkvz`` and of the taps, with fewer key heads
    than value heads and two blocks of heads; and with keys and values
    of two widths, neither a lane tile."""
    B, hk, hv, d, dv = sizes
    kernels(tokens, 2, a_pass)
    qkvz, w, weights = _stage_one(dtype, B, T, hk, hv, d, dv=dv)

    def ref(qkvz, w):
        return llama._gdn_chain_in(qkvz, w, hk, hv, d, dv)

    def got(qkvz, w):
        return _by_heads(module.chain_in(qkvz, w, hk, hv, d, dv), d, dv)

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
@pytest.mark.parametrize("T, tokens, heads, d", [
    (8, 8, 4, 16), (32, 8, 2, 16), (24, 12, 3, 16),
    # 30 heads of 192: six a step, a head a lane tile and a half
    (16, 8, 8, 192)])
def test_stage_two_is_the_expression(kernels, dtype, T, tokens, heads, d):
    """``hvd_gdn_chain_out_fwd`` / ``_bwd``: the gated norm and the
    gradients of ``o``, ``z`` and the gain."""
    B, H, eps = 2, {3: 6, 8: 30}.get(heads, 4), 1e-6
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
    if dtype == BF16:
        # the gain's gradient sums B x T x heads products: the kernel in
        # float32, the expression in bfloat16, which at 960 of them is
        # the further of the two from float32 on the same operands
        exact = jax.jit(jax.grad(loss(ref), 2))(
            o.astype(F32), z.astype(F32), gain.astype(F32))
        grads[1] = grads[1][:2] + (exact,)
    for name, a, b in zip(("d o", "d z", "d gain"), *grads):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


def _mixer(dtype, sizes=(2, 4, 16, 16), **more):
    """One ``linear_attention`` layer's leaves and an input: two key
    heads serving four value heads, 16 wide, four taps."""
    hk, hv, dk, dv = sizes
    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_head=16, d_ff=64, norm_eps=1e-6, conv_taps=4,
        layer_types=("linear_attention", "full_attention"),
        linear_key_heads=hk, linear_value_heads=hv, linear_key_dim=dk,
        linear_value_dim=dv, dtype=dtype, param_dtype=dtype, remat="attn",
        **more)
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


@pytest.mark.parametrize("sizes, more", [
    ((2, 4, 16, 16), {}),
    # a lane tile wide: the rule's kernels read the chain's ``[B, T, H
    # d]`` as it lies, one key head serving two value heads
    ((1, 2, 128, 128), {}),
    # keys 96 and values 192 wide on the strip, write strengths to 2,
    # no norm of the mixer's input
    ((2, 2, 96, 192), {"linear_beta_max": 2.0, "post_norm": "only"})],
    ids=["one-width", "a-lane-tile-wide", "two-widths"])
def test_the_mixer_is_the_same_on_both_carriers(kernels, monkeypatch, sizes,
                                                more):
    """``_gated_delta_net`` whole in float32, values and the gradients
    of its input and of every leaf it reads: the expressions and the
    scan, then the chain's kernels and the rule's."""
    cfg, lp, x = _mixer("float32", sizes, **more)
    assert ("gdn_norm" in lp) == (not more)
    ref = _mixer_readings(cfg, lp, x)
    kernels(16, 2, 8)
    monkeypatch.setattr(gated_delta_rule, "_INTERPRET", True)
    # eight heads a step a lane tile wide, else the whole width
    assert gated_delta_rule._token_major_step(*sizes) == sizes[1]
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


def test_keys_and_values_of_two_widths_take_the_strip_where_it_has_a_step(
        kernels, monkeypatch):
    """One width: the kernels. Two: the strip where ``[q | k]`` and
    ``v`` fall into blocks of whole lane groups of both widths (given
    the head counts), else the expression."""
    kernels()
    x = jnp.zeros((1, 8, 8))
    assert module.on_kernels(x, 16, 16) and not module.on_kernels(x, 8, 16)
    assert module._group(96) == module._group(192) == 384
    assert module._group(128) == 128 and module._group(64) == 128
    # 60 key heads of 96 are 15 groups, 30 value heads of 192 too: three
    # groups a step under 1152 lanes, one under 384
    assert module._strip_width(30, 30, 96, 192) == 1152
    assert module.on_kernels(x, 96, 192, 30, 30)
    monkeypatch.setattr(module, "LANES_A_STEP", 384)
    assert module._strip_width(30, 30, 96, 192) == 384
    assert module._strip_width(2, 2, 96, 192) == 384
    # 15 value heads of 192 end in the middle of a group; 3 key heads of
    # 96 make [q | k] a group and a half
    assert module._strip_width(30, 15, 96, 192) is None
    assert module._strip_width(3, 3, 96, 192) is None
    assert not module.on_kernels(x, 96, 192, 30, 15)
    qkvz, w, _ = _stage_one(F32, 1, 8, 3, 3, 96, dv=192)
    with pytest.raises(ValueError, match="whole lane groups"):
        module.chain_in(qkvz, w, 3, 3, 96, 192)
    # six heads of 192 a step are nine lane tiles; of 16 (the tests'),
    # the largest divisor
    monkeypatch.setattr(module, "HEADS_A_STEP", 8)
    assert module._heads_a_step(30, 192) == 6
    assert module._heads_a_step(32, 128) == 8
    assert module._heads_a_step(6, 16) == 6


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    kernels()
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        module.on_kernels(jnp.zeros((1, 8, 8)), 16, 16)


# ---------------------------------------------------------------------
# The seam between the chain and the rule: ``[B, T, H d]`` on both sides.
# ---------------------------------------------------------------------

def _seam_operands(hk, hv, d, T=128, seed=3):
    qkvz, taps, _ = _stage_one(F32, 1, T, hk, hv, d, seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    g = -0.3 * jax.random.uniform(ks[0], (1, T, hv), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[1], (1, T, hv)))
    gain = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
    weight = jax.random.normal(ks[3], (1, T, hv * d))
    return (qkvz, taps, g, beta, gain), weight


def test_chain_in_rule_chain_out_with_no_reshape_between(kernels,
                                                         monkeypatch):
    """``chain_in`` -> the rule -> ``chain_out``, each taking what the
    one before leaves, ``[B, T, H d]``, and a key head never repeated,
    against the expression it replaces: ``_gdn_chain_in`` by heads, the
    key heads repeated, the rule by heads on the scan, ``_gdn_chain_out``
    by heads. Values and the gradients of all five operands, float32,
    one key head serving two value heads a lane tile wide."""
    hk, hv, d, eps = 1, 2, 128, 1e-6
    operands, weight = _seam_operands(hk, hv, d)

    def seam(qkvz, taps, g, beta, gain):
        q, k, v, z = module.chain_in(qkvz, taps, hk, hv, d, d)
        o = gated_delta_rule.gated_delta_rule(q, k, v, g, beta,
                                              key_heads=hk)
        return module.chain_out(o, z, gain, eps)

    def expression(qkvz, taps, g, beta, gain):
        q, k, v, z = llama._gdn_chain_in(qkvz, taps, hk, hv, d, d)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        o = gated_delta_rule.gated_delta_rule(q, k, v, g, beta)
        return llama._gdn_chain_out(o, z, gain, eps).reshape(weight.shape)

    def readings(f):
        def loss(*x):
            out = f(*x)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, tuple(range(5)), has_aux=True))(*operands)
        return (out,) + grads

    with jax.default_matmul_precision("highest"):
        ref = readings(expression)
        kernels(16, 2, 8)
        monkeypatch.setattr(gated_delta_rule, "_INTERPRET", True)
        assert gated_delta_rule._token_major_step(hk, hv, d, d) == 2
        got = readings(seam)
    for name, a, b in zip(("out", "d qkvz", "d taps", "d g", "d beta",
                           "d gain"), got, ref):
        _close(a, b, F32, name)


def _eqns(jaxpr):
    """Every equation of a traced program, the bodies of its calls,
    checkpoints and custom derivatives too, but not a kernel's own."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_mixer_at_qwen3next_heads_copies_no_operand_of_the_rule(
        kernels, monkeypatch):
    """The traced program of two ``linear_attention`` mixers at
    Qwen3-Next's head geometry (16 key heads serving 32 value heads, 128
    wide; a short sequence), forward and gradient, kernels on: nothing
    repeats or broadcasts ``q`` or ``k`` to the value heads, nothing
    transposes an array of ``v``'s or ``o``'s size, and of ``q``'s size
    only ``k`` on its way into ``K K^T`` (part 1 of the rule) and that
    product's share of ``dk`` on its way back; the rule's kernels are
    ONE lowered function a form (the forward keeping its states and
    not, the backward) however many layers and phases call them."""
    hk, hv, d, T = 16, 32, 128, 128
    cfg, lp, _ = _mixer("bfloat16", (hk, hv, d, d))
    x = jnp.zeros((1, T, 32), BF16)
    kernels(64, 8, 32)
    monkeypatch.setattr(gated_delta_rule, "_INTERPRET", True)

    def two_layers(x, lp):
        for _ in range(2):
            x = x + llama._gated_delta_net(x, lp, cfg, None, None,
                                           jax.checkpoint)
        return jnp.sum(x.astype(F32))

    keys, values = T * hk * d, T * hv * d
    # a layer: ``k`` forward, again under the checkpoint, ``dk`` back
    for program, k_moves in ((two_layers, 2),
                             (jax.grad(two_layers, (0, 1)), 2 * 3)):
        moved = {keys: 0, values: 0}
        for eqn in _eqns(jax.make_jaxpr(program)(x, lp).jaxpr):
            name = eqn.primitive.name
            sizes = [v.aval.size for v in eqn.outvars]
            if name == "transpose" and sizes[0] in moved:
                moved[sizes[0]] += 1
            # a key head laid out for each value head it serves
            assert not (name in ("broadcast_in_dim", "gather", "concatenate")
                        and sizes[0] == T * hv * d
                        and eqn.outvars[0].aval.dtype == BF16), eqn
        assert moved == {keys: k_moves, values: 0}, moved
    text = jax.jit(jax.grad(two_layers, (0, 1))).lower(x, lp).as_text()
    assert text.count("func.func private @_kernel_fwd") == 2
    assert text.count("func.func private @_kernel_bwd") == 1
    # (the last layer's own forward is dead code under a linear loss)
    assert text.count("call @_kernel_fwd") >= 3
    assert text.count("call @_kernel_bwd") == 2
