"""Trinity-Mini (afmoe) through the normal llama path against the plain
float32 reference (horovod_tpu/models/reference.py): logits, loss and
every gradient leaf under each remat mode, the layer pattern scanned and
unrolled, the share of the experts (eight shares sum to the whole layer,
the shared expert counted once; no held slot dropped at any load), the
vocabulary slice. Small sizes, CPU. (That the older configurations build
what they always did: tests/single/test_older_configurations.py.)

What a case pays for (tests/conftest.py): ``_cfg()`` is the smallest
depth that holds every kind of layer once (a dense window layer, an
expert window layer, an expert full layer: three layers), and the remat
sweep, the share's other shapes, the vocabulary slice and the load cases
compile THAT; two periods and a layer left over are the one ``deeper``
case.

The tolerance is tests/single/test_olmoe_reference.py's: program and
reference both compute in float32 and differ in the order of float32
additions only; 2e-5 of the largest entry. Norm gains are drawn away
from 1, ``expert_bias`` away from 0 (it moves the choice of experts for
most of the tokens here), so that a norm left out, a bias that
reaches the weights, RoPE on a full layer or a window on the wrong layer
each move the result by whole percents.
"""

import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import (
    _ffn,
    llama_expert_load,
    llama_forward,
    moe_route,
)
from horovod_tpu.models.reference import (
    afmoe_expert_layer,
    afmoe_forward,
    afmoe_loss,
    afmoe_route,
)
from horovod_tpu.ops import grouped_moe

TOL = 2e-5
S, F = "sliding_attention", "full_attention"


def _cfg(**kw):
    """The cell's kinds of layer, each once: a leading dense layer (a
    window layer), an expert window layer and an expert full layer;
    experts 4..7 of 16 held. The cell's period (three window layers and
    a full one) and its repetition: the ``deeper`` case."""
    base = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4,
                n_kv_heads=2, d_head=32, d_ff=96, moe_d_ff=32,
                rope_theta=10000.0, n_experts=16, n_experts_per_token=4,
                n_dense_layers=1, layer_types=(S, S, F),
                sliding_window=6, n_shared_experts=1,
                score_func="sigmoid", norm_topk_prob=True,
                route_scale=2.826, scale_embed=True, attn_gate=True,
                post_norm=True, qk_norm="head", first_expert=4,
                n_experts_held=4, moe_impl="grouped", moe_aux_weight=0.0,
                dtype="float32", param_dtype="float32", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 32))
    for stack in ("dense_layers", "layers"):
        for name, w in params.get(stack, {}).items():
            if name.endswith("norm"):
                params[stack][name] = jax.random.uniform(
                    next(keys), w.shape, w.dtype, 0.5, 1.5)
    params["final_norm"] = jax.random.uniform(
        next(keys), params["final_norm"].shape, jnp.float32, 0.5, 1.5)
    if "expert_bias" in params["layers"]:
        b = params["layers"]["expert_bias"]
        params["layers"]["expert_bias"] = 0.3 * jax.random.normal(
            next(keys), b.shape, b.dtype)
    return params


def _batch(cfg, shape=(2, 16), seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _err(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                 1e-30))


# Compiled once a configuration (``cfg`` static), as the cell runs them:
# called eagerly, jax compiles these programs a primitive at a time
# (27 + 13 s for one configuration's two gradients where the jitted
# ones take 5 + 4). The EAGER call, which users make too, stays in
# tests/single/test_older_configurations.py.
_forward = jax.jit(llama_forward, static_argnums=2)
_ref_forward = jax.jit(afmoe_forward, static_argnums=2)
_loss = jax.jit(llama_loss, static_argnums=2)
_ref_loss = jax.jit(afmoe_loss, static_argnums=2,
                    static_argnames="vocab_rows")
_loss_and_grads = jax.jit(jax.value_and_grad(llama_loss), static_argnums=2)
_ref_loss_and_grads = jax.jit(jax.value_and_grad(afmoe_loss),
                              static_argnums=2)
_expert_load = jax.jit(llama_expert_load, static_argnums=2)


def _all_readings(forward, loss):
    """Logits, loss and gradients as ONE program a configuration."""
    return jax.jit(lambda params, batch, cfg: (
        forward(params, batch["tokens"], cfg),
        jax.value_and_grad(loss)(params, batch, cfg)), static_argnums=2)


_readings = _all_readings(llama_forward, llama_loss)
_ref_readings = _all_readings(afmoe_forward, afmoe_loss)


def _assert_model_matches(cfg, seed=0):
    params, batch = _params(cfg, seed), _batch(cfg)
    logits, (loss, grads) = _readings(params, batch, cfg)
    # the reference has no remat: one compile of it serves every mode
    ref_logits, (ref_loss, ref) = _ref_readings(
        params, batch, dataclasses.replace(cfg, remat=False))
    assert _err(logits, ref_logits) < TOL
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:   # it moves the choice, never a weight
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(r))
            continue
        assert np.any(np.asarray(r)), name
        assert _err(g, r) < TOL, name


@pytest.mark.parametrize("remat", [False, "attn", "attn+moe", "moe", True])
def test_logits_loss_and_every_gradient_leaf(remat):
    """Through the grouped path with a share of the experts, seeded
    weights and a seeded non-zero ``expert_bias``, under every remat
    mode the cell may use."""
    _assert_model_matches(_cfg(remat=remat))


@pytest.mark.parametrize("case", ["deeper", "all-held", "wide-window",
                                  "first-share", "bf16-fails"])
def test_the_layer_pattern_and_the_share_in_other_shapes(case):
    if case == "deeper":
        # 2 dense layers, then 9 expert layers: two whole periods of
        # (window, full, window, window) and one layer left over, every
        # one unrolled on its own slice of its stack.
        cfg = _cfg(n_layers=11, n_dense_layers=2,
                   layer_types=(S, S, S, F) * 2 + (S, S, S), remat="attn")
        assert [k[1:] for k in cfg.layer_kinds()[2:6]] == [
            (6, True), (0, False), (6, True), (6, True)]
    elif case == "all-held":
        cfg = _cfg(first_expert=0, n_experts_held=0)
    elif case == "wide-window":    # wider than the sequence: plain causal
        cfg = _cfg(sliding_window=64)
    elif case == "first-share":    # the cell's: experts 0..3
        cfg = _cfg(first_expert=0)
    else:
        cfg = _cfg(dtype="bfloat16")
        params, batch = _params(cfg), _batch(cfg)
        assert _err(_forward(params, batch["tokens"], cfg),
                    _ref_forward(params, batch["tokens"], cfg)) > 100 * TOL
        return
    _assert_model_matches(cfg)


def test_expert_bias_moves_the_choice_and_never_the_weights():
    cfg = _cfg()
    lp = jax.tree.map(lambda w: w[0], _params(cfg)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    w, idx, _ = moe_route(h, lp["router"], 4, True, "sigmoid",
                          lp["expert_bias"], 2.826)
    w0, idx0, _ = moe_route(h, lp["router"], 4, True, "sigmoid", None,
                            2.826)
    moved = np.any(np.sort(idx, -1) != np.sort(idx0, -1), -1)
    assert moved.mean() > 0.5
    np.testing.assert_allclose(w.sum(-1), 2.826, rtol=1e-5)
    s = jax.nn.sigmoid(h @ lp["router"])
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        w, 2.826 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    dense = jnp.zeros_like(s).at[
        jnp.arange(2)[:, None, None], jnp.arange(16)[None, :, None],
        idx].set(w)
    assert _err(dense, afmoe_route(h, lp, cfg)) < TOL


def test_eight_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The guide's share test: the routed parts all eight shares give,
    plus what every chip computes alike (the shared expert) counted
    ONCE, equal the uncut reference's output for the whole layer. The
    program's share against the reference's share on the way."""
    whole = _cfg(first_expert=0, n_experts_held=0)
    lp = jax.tree.map(lambda w: w[1], _params(whole)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, whole.d_model))
    shared, routed = afmoe_expert_layer(h, lp, whole)
    total = jnp.zeros_like(routed)
    for share in range(8):
        cfg = _cfg(first_expert=2 * share, n_experts_held=2)
        held = dict(lp, **{name: lp[name][2 * share:2 * share + 2]
                           for name in ("moe_gate", "moe_up", "moe_down")})
        shared_s, routed_s = afmoe_expert_layer(h, held, cfg)
        assert _err(shared_s, shared) == 0.0
        got, _ = _ffn(h, held, cfg)            # Shared(h) + its routed part
        assert _err(got, shared_s + routed_s) < TOL
        total = total + (got - shared_s)
    assert _err(shared + total, shared + routed) < TOL
    uncut, _ = _ffn(h, lp, whole)
    assert _err(uncut, shared + routed) < TOL


def test_loss_over_the_vocabulary_slice():
    """A chip that holds rows 0..31 of a vocabulary of 128 (ids and
    targets drawn from the slice) reads the uncut model's loss with the
    other logits removed."""
    uncut = _cfg()
    cfg = dataclasses.replace(uncut, vocab_size=32)
    full = _params(uncut)
    held = dict(full, embed=full["embed"][:32],
                lm_head=full["lm_head"][:, :32])
    batch = _batch(cfg)
    want = _ref_loss(full, batch, uncut, vocab_rows=32)
    assert abs(float(_loss(held, batch, cfg)) - float(want)) \
        < TOL * float(want)
    assert abs(float(_ref_loss(held, batch, cfg)) - float(want)) \
        < TOL * float(want)
    assert abs(float(_ref_loss(full, batch, uncut)) - float(want)) > 0.1


@pytest.mark.parametrize("load", ["all", "none", "even"])
def test_no_held_slot_is_dropped_at_any_load(load):
    """Every token chooses held experts only (4 x the even share: every
    chunk of the sorted slots holds held rows), none does, and the even
    case inside the first chunk; values and gradients against the
    reference each time."""
    cfg = _cfg(n_experts_held=2)       # 2 of 16 held, 4 a token
    params = _params(cfg)
    # the load is DATA: one compiled program serves the three
    bias = jnp.zeros_like(params["layers"]["expert_bias"])
    if load != "even":
        bias = jnp.full_like(bias, 4.0 if load == "none" else -4.0) \
            .at[:, 4:6].set(-4.0 if load == "none" else 4.0)
    params["layers"]["expert_bias"] = bias
    batch = _batch(cfg, (2, 128))
    loss, grads = _loss_and_grads(params, batch, cfg)
    ref_loss, ref = _ref_loss_and_grads(params, batch, cfg)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    for name in ("moe_gate", "moe_down", "router", "shared_up", "wg"):
        assert _err(grads["layers"][name], ref["layers"][name]) < TOL, name
    held = np.asarray(_expert_load(params, batch["tokens"], cfg))[
        :, 4:6].sum(-1)
    slots = batch["tokens"].size * 4
    chunk = slots // (16 // (2 * grouped_moe._HELD_ROW_BOUND))
    assert chunk == 256 == 2 * slots * 2 // 16
    if load == "all":     # 2 of a token's 4 choices can be held: all are
        assert np.all(held == slots // 2) and slots // 2 == 2 * chunk
    elif load == "none":
        assert np.all(held == 0)
        assert not np.any(np.asarray(grads["layers"]["moe_up"]))
    else:
        assert np.all((held > 0) & (held <= chunk))


def _share_layer(rows, held=2):
    """One expert layer that holds experts 4 and 5 of 16 (``held`` of
    them from 4 on), 256 tokens, and a hand-made routing that sends
    exactly ``rows`` of the 1024 slots to experts 4 and 5 (chunks of
    256 where two are held)."""
    cfg = _cfg(n_experts_held=held, first_expert=4)
    lp = jax.tree.map(lambda w: w[0], _params(cfg)["layers"])
    ks = jax.random.split(jax.random.PRNGKey(rows), 3)
    hf = jax.random.normal(ks[0], (256, cfg.d_model))
    # slot i goes to held expert 4 or 5 if i < rows, else to 0..3 / 6..15
    held = (jnp.arange(1024) < rows)[jax.random.permutation(ks[1], 1024)]
    pick = jax.random.randint(ks[2], (1024,), 0, 14)
    idx = jnp.where(held, 4 + pick % 2, jnp.where(pick < 4, pick, pick + 2))
    idx = idx.reshape(256, 4).astype(jnp.int32)
    w = jax.random.uniform(ks[2], (256, 4), jnp.float32, 0.5, 1.5)
    return cfg, lp, hf, idx, w


def _dense_share(hf, w, lp, idx):
    """The two held experts' weighted outputs summed per token, by a
    dense count."""
    out = 0.0
    for e in range(2):
        we = jnp.sum(jnp.where(idx == 4 + e, w, 0.0), -1)
        y = (jax.nn.silu(hf @ lp["moe_gate"][e])
             * (hf @ lp["moe_up"][e])) @ lp["moe_down"][e]
        out = out + we[:, None] * y
    return out


@pytest.fixture(scope="module")
def share_and_its_vjp():
    """``block -> program``: ``(hf, w, lp, idx, cot) -> (the share's
    output, its gradients; the dense count's output, its gradients)``,
    compiled once a block: the routing ``idx`` is DATA, and the twelve
    cases below differ in nothing else. ``_HELD_BLOCK`` is read while
    tracing, so each block has a FUNCTION of its own that sets it: jax
    keys its trace cache on the function and the operands' avals, which
    are the same for every block, and one function jitted twice would
    run the first block's program for both."""
    cfg = _share_layer(0)[0]

    @functools.cache
    def program(block):
        def both(hf, w, lp, idx, cot):
            with mock.patch.object(grouped_moe, "_HELD_BLOCK", block):
                got, vjp = jax.vjp(
                    lambda hf, w, lp: grouped_moe._held_experts_ffn(
                        hf, lp, cfg, w, idx), hf, w, lp)
                grads = vjp(cot)
            ref, ref_vjp = jax.vjp(lambda hf, w, lp: _dense_share(
                hf, w, lp, idx), hf, w, lp)
            return got, grads, ref, ref_vjp(cot)
        return jax.jit(both)

    return program


@pytest.mark.parametrize("block", [64, 2048])
@pytest.mark.parametrize("rows", [0, 100, 256, 257, 700, 1024])
def test_the_chunks_of_the_share_cover_exactly_the_held_rows(
        rows, block, share_and_its_vjp):
    """``_held_experts_ffn`` alone with a hand-made routing that sends
    exactly ``rows`` of 1024 slots to the 2 held experts (chunks of 256:
    none, part of the first, the first whole, one row into the second,
    into the third, all four): the held experts' weighted outputs,
    summed per token, and their gradients, against a dense count. The
    rows of a chunk are gathered in four blocks of 64, and as one block
    (2048, the chip's, does not divide 256)."""
    cfg, lp, hf, idx, w = _share_layer(rows)
    assert int(jnp.sum((idx == 4) | (idx == 5))) == rows
    cot = jax.random.normal(jax.random.PRNGKey(rows + 1), hf.shape)
    got, grads, ref, ref_grads = share_and_its_vjp(block)(hf, w, lp, idx,
                                                          cot)
    assert _err(got, ref) < TOL or (rows == 0 and not np.any(got))
    for g, r, name in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads),
                          ["hf", "w"] + sorted(lp)):
        if np.any(np.asarray(r)):
            assert _err(g, r) < TOL, name
        else:
            assert not np.any(np.asarray(g)), name


def _equations(jaxpr, inside_while=False):
    """(equation, inside a ``while``) of ``jaxpr`` and the jaxprs it
    holds."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_while
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(
                sub, inside_while or eqn.primitive.name == "while")


def _row_movements(jaxpr):
    """(primitive, rows moved, inside a ``while``) of every gather and
    scatter-add of whole rows in ``jaxpr`` and the jaxprs it holds."""
    for eqn, inside_while in _equations(jaxpr):
        name = eqn.primitive.name
        if name in ("gather", "scatter-add"):
            moved = (eqn.outvars[0] if name == "gather"
                     else eqn.invars[2]).aval
            if moved.ndim == 2:
                yield name, moved.shape[0], inside_while


def test_each_block_of_the_share_is_a_program_of_its_own(share_and_its_vjp):
    """The two programs the twelve cases above run gather what their
    block says: rows in blocks of 64, and a chunk of 256 as one block
    under the chip's 2048 (which does not divide it). Were one traced
    function served for both, the second set would be the first's."""
    cfg, lp, hf, idx, w = _share_layer(700)
    gathered = {
        block: {rows for name, rows, _ in _row_movements(
            share_and_its_vjp(block).trace(hf, w, lp, idx, hf).jaxpr.jaxpr)
            if name == "gather"}
        for block in (64, 2048)}
    assert gathered == {64: {64}, 2048: {256}}, gathered


def test_the_shares_gathers_are_no_chunk_long_and_its_sums_are(monkeypatch):
    """A count: in the jaxpr of the share's gradient every gather of
    rows runs inside a ``while`` over blocks of 64 rows (two in the
    first chunk; three in the later chunks' loops, whose backward
    gathers the rows again), none over a chunk of 256; the scatter-adds
    take a chunk each: in blocks they cost three times as much a row on
    the chip (``grouped_moe._sum_held``). The first chunk's two stand
    outside any loop; the later chunks' three inside the loop over the
    chunks that held rows reach, one forward and two backward (the sum
    of the chunk run again, which nothing reads and the compiler drops,
    and the tokens' gradient)."""
    monkeypatch.setattr(grouped_moe, "_HELD_BLOCK", 64)
    cfg, lp, hf, idx, w = _share_layer(700)

    def loss(hf, w, lp):
        return jnp.sum(grouped_moe._held_experts_ffn(hf, lp, cfg, w, idx))

    moves = sorted(_row_movements(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(hf, w, lp).jaxpr))
    assert moves == [("gather", 64, True)] * 5 \
        + [("scatter-add", 256, False)] * 2 \
        + [("scatter-add", 256, True)] * 3, moves


@pytest.mark.parametrize("held,chunks", [(4, 2), (2, 4), (1, 8)])
def test_nothing_is_stacked_by_chunk(held, chunks):
    """The later chunks are a loop whose trip count follows the rows
    held, not a differentiated ``lax.scan`` over all of them (which
    stacked the tokens and the three expert matrices once a later chunk
    as residuals, 1.88 GB a layer of the Qwen3-Next cell, whether a
    chunk ran or not): in the jaxpr of the share's gradient, at the
    cells' 2, 4 and 8 chunks, no ``scan``, and no value of the tokens'
    or an expert matrix's shape with a leading ``chunks - 1`` (or
    ``chunks``)."""
    cfg, lp, hf, idx, w = _share_layer(700, held=held)
    assert cfg.n_experts // (held * grouped_moe._HELD_ROW_BOUND) == chunks

    def loss(hf, w, lp):
        return jnp.sum(grouped_moe._held_experts_ffn(hf, lp, cfg, w, idx))

    eqns = [eqn for eqn, _ in _equations(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(hf, w, lp).jaxpr)]
    names = {eqn.primitive.name for eqn in eqns}
    assert "scan" not in names and "while" in names
    whole = {hf.shape} | {lp[k].shape for k in
                          ("moe_gate", "moe_up", "moe_down")}
    shapes = [(eqn.primitive.name, getattr(v.aval, "shape", ()))
              for eqn in eqns for v in eqn.outvars]
    stacked = [(name, s) for name, s in shapes
               if len(s) > 2 and s[0] in (chunks - 1, chunks)
               and s[1:] in whole]
    assert not stacked, stacked


def test_a_share_needs_the_grouped_dispatch():
    cfg = _cfg(moe_impl="gshard")
    with pytest.raises(ValueError, match="grouped"):
        llama_loss(_params(cfg), _batch(cfg), cfg)


@pytest.mark.parametrize("bad", [
    dict(layer_types=(S, F)), dict(layer_types=("banded",) * 3),
    dict(sliding_window=0), dict(n_dense_layers=3),
    dict(score_func="tanh"), dict(qk_norm="row"),
    dict(first_expert=14, n_experts_held=4), dict(n_experts_held=-1),
])
def test_a_configuration_that_makes_no_sense_is_refused(bad):
    with pytest.raises(ValueError):
        _cfg(**bad)


@pytest.mark.parametrize("field", [
    dict(sliding_window=8, layer_types=(S,) * 2), dict(attn_gate=True),
    dict(n_experts=8, first_expert=0, n_experts_held=2),
    dict(n_experts=8, n_dense_layers=1), dict(post_norm=True),
    dict(scale_embed=True), dict(qk_norm="head"),
    dict(n_experts=8, score_func="sigmoid"),
    dict(n_experts=8, n_shared_experts=1), dict(d_head=32),
])
def test_decode_and_serving_refuse_what_only_training_implements(field):
    """Training-only until serving has them: a clear ValueError from
    every entry point of models/generate.py and from the serving
    engine, never a silently ignored field."""
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert cfg.training_only_fields()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_generate(params, prompt, cfg, 2)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_decode_step(params, prompt[:, 0], None, None, None, cfg)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    assert not LlamaConfig.tiny().training_only_fields()
    assert not LlamaConfig.tiny(qk_norm=True).training_only_fields()


def test_a_layer_pattern_has_no_pipeline_schedule():
    from horovod_tpu.models.llama import _validate_pipeline

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match="layer pattern"):
        _validate_pipeline(_cfg(), 2, mesh, "seq", 2)
