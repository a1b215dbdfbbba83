"""LFM2-8B-A1B (lfm2_moe) through the normal llama path against the
plain float32 reference (horovod_tpu/models/reference.py): logits, loss
and every gradient leaf under each remat mode, conv layers beside
attention layers unrolled over parameter stacks by kind of layer and a
uniform conv stack scanned, dense and expert FFNs, the tied and sliced
vocabulary, the share of the experts with no shared expert beside it
(four shares sum to the whole layer), and the convolution's causality.
Small sizes, CPU. (That the older configurations build what they always
did: tests/single/test_older_configurations.py.)

What a case pays for (tests/conftest.py): ``_cfg()`` is the smallest
depth that holds every kind of layer and every parameter stack (the
leading dense conv layer and ONE period: five layers), and the remat
sweep, the share's other shapes and the vocabulary slice compile THAT;
the period's repetition (stacks two deep beside a stack six deep) is the
one ``published-head`` case, two dense conv layers and two periods.

The tolerance is tests/single/test_afmoe_reference.py's: program and
reference both compute in float32 and differ in the order of float32
additions only; 2e-5 of the largest entry. Norm gains are drawn away
from 1 and ``expert_bias`` away from 0, so that a norm left out, RoPE
left off an attention layer, a tap applied to the wrong position or a
gate forgotten each move the result by whole percents.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import (
    _ffn,
    _short_conv,
    llama_forward,
    llama_partition_rules,
)
from horovod_tpu.models.reference import (
    lfm2_expert_layer,
    lfm2_forward,
    lfm2_loss,
    lfm2_short_conv,
)

TOL = 2e-5
C, A = "conv", "full_attention"
STACKS = ("dense_conv_layers", "dense_layers", "conv_layers", "layers")


def _cfg(**kw):
    """The cell's shape in small: a leading dense conv layer, then ONE
    period of (attention, conv, conv, conv); experts 2..3 of 8 held;
    heads 16 wide; the head tied to the embedding."""
    base = dict(vocab_size=128, d_model=64, n_layers=5, n_heads=4,
                n_kv_heads=2, d_ff=96, moe_d_ff=32, rope_theta=1e6,
                n_experts=8, n_experts_per_token=4, n_dense_layers=1,
                layer_types=(C,) + (A, C, C, C), conv_taps=3,
                rope_full_attention=True, tie_embeddings=True,
                score_func="sigmoid", norm_topk_prob=True,
                route_scale=1.0, qk_norm="head", first_expert=2,
                n_experts_held=2, moe_impl="grouped", moe_aux_weight=0.0,
                dtype="float32", param_dtype="float32", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    for stack in STACKS:
        for name, w in params.get(stack, {}).items():
            if name.endswith("norm"):
                params[stack][name] = jax.random.uniform(
                    next(keys), w.shape, w.dtype, 0.5, 1.5)
            elif name == "expert_bias":
                params[stack][name] = 0.3 * jax.random.normal(
                    next(keys), w.shape, w.dtype)
    params["final_norm"] = jax.random.uniform(
        next(keys), params["final_norm"].shape, jnp.float32, 0.5, 1.5)
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
# called eagerly, jax compiles these programs a primitive at a time. The
# EAGER call, which users make too, stays in
# tests/single/test_older_configurations.py.
_forward = jax.jit(llama_forward, static_argnums=2)
_ref_forward = jax.jit(lfm2_forward, static_argnums=2)
_loss = jax.jit(llama_loss, static_argnums=2)
_ref_loss = jax.jit(lfm2_loss, static_argnums=2,
                    static_argnames="vocab_rows")
_grads = jax.jit(jax.grad(llama_loss), static_argnums=2)


def _all_readings(forward, loss):
    """Logits, loss and gradients as ONE program a configuration."""
    return jax.jit(lambda params, batch, cfg: (
        forward(params, batch["tokens"], cfg),
        jax.value_and_grad(loss)(params, batch, cfg)), static_argnums=2)


_readings = _all_readings(llama_forward, llama_loss)
_ref_readings = _all_readings(lfm2_forward, lfm2_loss)


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
    """Conv and attention layers, a dense and eight expert FFNs, the
    share, the tied head (its gradient the sum of both uses), under
    every remat mode the cell may use."""
    _assert_model_matches(_cfg(remat=remat))


@pytest.mark.parametrize("case", [
    "published-head", "all-held", "first-share", "conv-only",
    "attention-leads", "dense-hybrid", "rope-off-fails", "bf16-fails"])
def test_the_layer_pattern_and_the_share_in_other_shapes(case):
    if case == "published-head":
        # the published model's first ten layers: two dense conv layers,
        # then (attention, conv, conv, conv) twice
        cfg = _cfg(n_layers=10, n_dense_layers=2,
                   layer_types=(C, C) + (A, C, C, C) * 2, remat="attn")
        assert [s[:2] for s in cfg.layer_plan()[:4]] == [
            ("dense_conv_layers", 0), ("dense_conv_layers", 1),
            ("layers", 0), ("conv_layers", 0)]
    elif case == "all-held":
        cfg = _cfg(first_expert=0, n_experts_held=0)
    elif case == "first-share":    # the cell's: experts 0..1
        cfg = _cfg(first_expert=0)
    elif case == "conv-only":
        # one kind of layer, no experts: ONE scan over ``conv_layers``
        cfg = _cfg(n_layers=3, n_dense_layers=0, n_experts=0,
                   first_expert=0, n_experts_held=0, layer_types=(C,) * 3,
                   score_func="softmax")
        assert sorted(_params(cfg)) == ["conv_layers", "embed",
                                        "final_norm"]
        assert "scan" in str(jax.make_jaxpr(
            lambda p, t: llama_forward(p, t, cfg))(
                _params(cfg), _batch(cfg)["tokens"]))
    elif case == "attention-leads":   # a dense ATTENTION layer leads
        cfg = _cfg(n_layers=4, layer_types=(A, C, A, C))
        assert [s.stack for s in cfg.layer_plan()] == [
            "dense_layers", "conv_layers", "layers", "conv_layers"]
    elif case == "dense-hybrid":      # no experts at all
        cfg = _cfg(n_layers=4, n_dense_layers=0, n_experts=0,
                   first_expert=0, n_experts_held=0,
                   layer_types=(C, A, C, C), score_func="softmax")
    else:
        wrong = {"rope-off-fails": dict(rope_full_attention=False),
                 "bf16-fails": dict(dtype="bfloat16")}[case]
        cfg = _cfg(**wrong)
        params, batch = _params(cfg), _batch(cfg)
        assert _err(_forward(params, batch["tokens"], cfg),
                    _ref_forward(params, batch["tokens"], cfg)) > 100 * TOL
        return
    _assert_model_matches(cfg)


def test_parameter_stacks_by_kind_of_layer():
    """A conv layer has ``conv_in``, ``conv_w``, ``conv_out`` and no
    ``wq`` .. ``wo``, and the other way round; the tied model has no
    ``lm_head``; every leaf finds a partition rule of its own rank."""
    import re

    # two periods, as the cell repeats them: nothing is compiled here
    cfg = _cfg(n_layers=9, layer_types=(C,) + (A, C, C, C) * 2)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert sorted(params) == ["conv_layers", "dense_conv_layers", "embed",
                              "final_norm", "layers"]
    conv = {"conv_norm", "conv_in", "conv_w", "conv_out", "mlp_norm"}
    attn = {"attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
            "mlp_norm"}
    experts = {"router", "expert_bias", "moe_gate", "moe_up", "moe_down"}
    assert set(params["dense_conv_layers"]) == conv | {"w_gate", "w_up",
                                                       "w_down"}
    assert set(params["conv_layers"]) == conv | experts
    assert set(params["layers"]) == attn | experts
    shapes = {k: v.shape for k, v in params["conv_layers"].items()}
    assert (shapes["conv_in"], shapes["conv_w"], shapes["conv_out"]) == (
        (6, 64, 192), (6, 3, 64), (6, 64, 64))
    assert params["layers"]["wq"].shape == (2, 64, 64)
    assert params["layers"]["q_norm"].shape == (2, 16)
    assert params["layers"]["moe_gate"].shape == (2, 2, 64, 32)
    assert params["dense_conv_layers"]["w_gate"].shape == (1, 64, 96)
    assert [(s.stack, s.index, s.mixer, s.rope) for s in cfg.layer_plan()] \
        == [("dense_conv_layers", 0, "conv", False),
            ("layers", 0, "attention", True),
            ("conv_layers", 0, "conv", False),
            ("conv_layers", 1, "conv", False),
            ("conv_layers", 2, "conv", False),
            ("layers", 1, "attention", True),
            ("conv_layers", 3, "conv", False),
            ("conv_layers", 4, "conv", False),
            ("conv_layers", 5, "conv", False)]
    # what the benchmark's accepted adapter reads stays a 3-tuple
    assert cfg.layer_kinds()[:2] == [(True, 0, False), (False, 0, True)]
    rules = llama_partition_rules()
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        name = "/".join(str(k.key) for k in path)
        spec = next(spec for pat, spec in rules if re.search(pat, name))
        assert len(spec) == leaf.ndim, (name, spec)


def test_four_shares_are_the_whole_layer():
    """The guide's share test: the routed parts all four shares give
    equal the uncut reference's output for the whole layer (no shared
    expert to count once). The program's share against the reference's
    share on the way."""
    whole = _cfg(first_expert=0, n_experts_held=0)
    lp = jax.tree.map(lambda w: w[1], _params(whole)["conv_layers"])
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, whole.d_model))
    routed = lfm2_expert_layer(h, lp, whole)
    total = jnp.zeros_like(routed)
    for share in range(4):
        cfg = _cfg(first_expert=2 * share, n_experts_held=2)
        held = dict(lp, **{name: lp[name][2 * share:2 * share + 2]
                           for name in ("moe_gate", "moe_up", "moe_down")})
        routed_s = lfm2_expert_layer(h, held, cfg)
        assert np.any(np.asarray(routed_s))
        got, _ = _ffn(h, held, cfg)
        assert _err(got, routed_s) < TOL
        total = total + got
    assert _err(total, routed) < TOL
    uncut, _ = _ffn(h, lp, whole)
    assert _err(uncut, routed) < TOL


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_convolution_is_causal_and_three_tokens_long(which):
    """A change of the input at position t moves no output before t,
    and none after t + 2 (through the convolution alone: no attention,
    no norm over positions)."""
    cfg = _cfg()
    lp = jax.tree.map(lambda w: w[2], _params(cfg)["conv_layers"])
    run = (lambda h: _short_conv(h, lp, cfg)) if which == "program" \
        else (lambda h: lfm2_short_conv(h, lp))
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    t = 5
    moved = np.abs(np.asarray(
        run(h.at[:, t].add(1.0)) - run(h))).max((0, 2))       # [T]
    assert not moved[:t].any() and not moved[t + 3:].any()
    assert (moved[t:t + 3] > 1e-3).all()
    # each tap at its own position: w_0 meets u_{t-2}, so with w_0
    # alone position t reaches t + 2 (and t itself through the gate C),
    # not t + 1
    only = dict(lp, conv_w=jnp.zeros_like(lp["conv_w"]).at[0].set(1.0))
    run0 = (lambda h: _short_conv(h, only, cfg)) if which == "program" \
        else (lambda h: lfm2_short_conv(h, only))
    moved = np.abs(np.asarray(
        run0(h.at[:, t].add(1.0)) - run0(h))).max((0, 2))
    assert moved[t + 2] > 1e-3 and moved[t] > 1e-3
    assert not moved[t + 1] and not moved[:t].any()
    assert _err(_short_conv(h, lp, cfg), lfm2_short_conv(h, lp)) < TOL


def test_loss_over_the_tied_vocabulary_slice():
    """A chip that holds rows 0..31 of a tied vocabulary of 128 (ids and
    targets drawn from the slice) reads the uncut model's loss with the
    other logits removed; the ONE matrix serves lookup and head."""
    uncut = _cfg()
    cfg = dataclasses.replace(uncut, vocab_size=32)
    full = _params(uncut)
    held = dict(full, embed=full["embed"][:32])
    batch = _batch(cfg)
    want = _ref_loss(full, batch, uncut, vocab_rows=32)
    assert abs(float(_loss(held, batch, cfg)) - float(want)) \
        < TOL * float(want)
    assert abs(float(_ref_loss(held, batch, cfg)) - float(want)) \
        < TOL * float(want)
    assert abs(float(_ref_loss(full, batch, uncut)) - float(want)) > 0.1
    # the tied gradient is the sum of both uses: the lookup's alone (the
    # head cut off the graph) and the head's alone differ from it
    g = _grads(held, batch, cfg)["embed"]
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    apart = _grads(dict(held, lm_head=held["embed"].T), batch, untied)
    assert _err(g, apart["embed"] + apart["lm_head"].T) < TOL
    assert _err(g, apart["embed"]) > 0.1 and _err(g, apart["lm_head"].T) \
        > 1e-3


@pytest.mark.parametrize("bad", [
    dict(conv_taps=0), dict(layer_types=(A,) * 5),
    dict(layer_types=("convolution",) * 5)])
def test_conv_layers_and_their_taps_come_together(bad):
    with pytest.raises(ValueError):
        _cfg(**bad)


@pytest.mark.parametrize("field", [
    dict(layer_types=(C, C), conv_taps=3),
    dict(layer_types=(A, A), rope_full_attention=True),
    dict(tie_embeddings=True)])
def test_decode_and_serving_refuse_a_conv_layer_and_a_tied_head(field):
    """No state cache beside the key/value blocks, no tied head in the
    decode path: a clear ValueError, never a silently ignored field."""
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_generate(params, prompt, cfg, 2)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)


@pytest.mark.parametrize("field", [
    dict(layer_types=(C, C), conv_taps=3), dict(tie_embeddings=True)])
def test_conv_layers_and_a_tied_head_have_no_pipeline_schedule(field):
    from horovod_tpu.models.llama import _validate_pipeline

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match="no pipeline schedule"):
        _validate_pipeline(LlamaConfig.tiny(**field), 2, mesh, "seq", 2)
