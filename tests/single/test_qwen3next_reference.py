"""Qwen3-Next-80B-A3B (qwen3_next) through the normal llama path against
the plain float32 reference (horovod_tpu/models/reference.py, whose
delta rule runs token by token): logits, loss and every gradient leaf
under each remat mode for the published pattern (linear_attention x 3,
full_attention), RoPE over a part of a head against a rotation written
out by hand, the shared expert's gate, the share of the experts tied to
the model (four shares and ONE shared expert sum to the uncut layer),
the new stack under ``layer_plan`` and the partition rules, and what
the configuration refuses. Small sizes, CPU. (That the older
configurations lower to the text they lowered to:
tests/single/test_older_configurations.py.)

Tolerance: program and reference both compute in float32. The attention
layer and the experts differ in the order of float32 additions (2e-5 of
the largest entry, tests/single/test_afmoe_reference.py's); the
linear_attention layers besides in the chunked form's triangular solve,
and their gradients pass an L2 norm and an RMSNorm of small vectors,
which multiply a rounding by the inverse of the vector's length: 5e-3 of
the largest entry there, a hundredth of what a tap on the wrong
position, a gate left out or a head served by the wrong key head move.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import (
    _ffn,
    _rope,
    llama_forward,
    llama_partition_rules,
)
from horovod_tpu.models.reference import (
    qwen3next_expert_layer,
    qwen3next_forward,
    qwen3next_loss,
)

TOL, GDN_TOL = 2e-5, 5e-3
L, A = "linear_attention", "full_attention"
STACKS = ("linear_layers", "layers")


def _cfg(**kw):
    """The cell's shape in small: one period, linear_attention x 3 then
    full_attention; two key heads serving four value heads; heads 16
    wide of which 4 dimensions turn; experts 4..7 of 16 held beside a
    gated shared expert; an untied head."""
    base = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
                n_kv_heads=2, d_head=16, d_ff=96, moe_d_ff=32,
                rope_theta=1e7, norm_eps=1e-6, n_experts=16,
                n_experts_per_token=3, layer_types=(L, L, L, A),
                conv_taps=4, linear_key_heads=2, linear_value_heads=4,
                linear_key_dim=8, linear_value_dim=16, partial_rotary=4,
                rope_full_attention=True, qk_norm="head", attn_gate=True,
                n_shared_experts=1, shared_expert_gate=True,
                score_func="softmax", norm_topk_prob=True, first_expert=4,
                n_experts_held=4, moe_impl="grouped", moe_aux_weight=0.0,
                dtype="float32", param_dtype="float32", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    """Seeded weights with the norm gains drawn away from 1, so that a
    norm left out moves the result."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    for stack in STACKS:
        for name, w in params.get(stack, {}).items():
            if name.endswith("norm"):
                params[stack][name] = jax.random.uniform(
                    next(keys), w.shape, w.dtype, 0.5, 1.5)
    params["final_norm"] = jax.random.uniform(
        next(keys), params["final_norm"].shape, jnp.float32, 0.5, 1.5)
    return params


def _batch(cfg, shape=(2, 128), seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _err(got, ref):
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


@functools.lru_cache(maxsize=None)
def _reference_readings():
    """The reference's logits, loss and gradients: once for all modes."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        return (jax.jit(lambda p: qwen3next_forward(
                    p, batch["tokens"], cfg))(params),
                *jax.jit(jax.value_and_grad(
                    lambda p: qwen3next_loss(p, batch, cfg)))(params))


@pytest.mark.parametrize("remat", [False, "attn", "attn/ffn", True])
def test_logits_loss_and_every_gradient_leaf(remat):
    cfg = _cfg(remat=remat)
    params, batch = _params(cfg), _batch(cfg)
    ref_logits, ref_loss, ref = _reference_readings()
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: llama_forward(
            p, batch["tokens"], cfg))(params) if remat is False \
            else ref_logits
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg)))(params)
    assert _err(logits, ref_logits) < GDN_TOL
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == 17 + 17 + 3
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        r = ref
        for key in path:
            r = r[key.key]
        assert float(jnp.max(jnp.abs(r))) > 0, name
        assert _err(g, r) < GDN_TOL, (name, _err(g, r))


@pytest.mark.parametrize("remat,regions", [("attn", [2] * 4),
                                           ("attn/ffn", [6, 1, 2] * 3
                                            + [1, 2])])
def test_remat_attn_ffn_checkpoints_a_linear_mixer_in_two_stages(
        remat, regions):
    """What each checkpoint of the forward program hands on: under
    "attn" a layer at a time (the stream and the aux term); under
    "attn/ffn" a linear_attention layer in three, what the rule reads
    (q, k at their key heads and v token-major, z, g, beta), the mixer's
    output, the
    FFN's, and the attention layer in two."""
    cfg = _cfg(remat=remat)
    params = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    program = jax.make_jaxpr(lambda p, t: llama_forward(p, t, cfg))(
        params, jax.ShapeDtypeStruct((2, 64), jnp.int32))
    found = [e for e in program.jaxpr.eqns if e.primitive.name == "remat2"]
    assert [len(e.outvars) for e in found] == regions
    if remat == "attn/ffn":
        assert [v.aval.shape for v in found[0].outvars] == [
            (2, 64, 16), (2, 64, 16), (2, 64, 64), (2, 64, 4, 16),
            (2, 64, 4), (2, 64, 4)]


def test_the_attention_layer_and_the_experts_alone_meet_to_rounding():
    """Without a linear layer in front the tight bound holds: the gated
    attention at a quarter of a head's dimensions turned, and the gated
    shared expert beside the share."""
    cfg = _cfg(n_layers=2, layer_types=(A, A), conv_taps=0,
               linear_key_heads=0, linear_value_heads=0, linear_key_dim=0,
               linear_value_dim=0)
    params, batch = _params(cfg), _batch(cfg, (2, 16))
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(
            lambda p: llama_loss(p, batch, cfg)))(params)
        ref = jax.jit(jax.grad(
            lambda p: qwen3next_loss(p, batch, cfg)))(params)
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(ref)):
        assert _err(g, r) < TOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize("rotary", [4, 8, 16, 0])
def test_rope_turns_the_first_dimensions_of_a_head_and_passes_the_rest(
        rotary):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(5), (2, 5))
    got = np.asarray(_rope(x, positions, 1e4, rotary))
    r = rotary or 16
    want = np.array(x)
    for t in range(5):
        for i in range(r // 2):          # the pair (i, i + r/2) turns
            angle = t * 1e4 ** (-i / (r // 2))
            a, b = np.asarray(x[:, t, :, i]), np.asarray(x[:, t, :,
                                                           i + r // 2])
            want[:, t, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, t, :, i + r // 2] = a * np.sin(angle) + b * np.cos(angle)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (got[..., r:] == np.asarray(x)[..., r:]).all()
    # the whole head named outright is the whole head
    if rotary == 16:
        np.testing.assert_array_equal(
            got, np.asarray(_rope(x, positions, 1e4)))


def test_the_shared_experts_gate():
    """``y = routed + sigmoid(h w_sg) * SwiGLU_shared(h)``: a gate of
    -inf leaves the routed part, a gate of 0 half the shared expert."""
    cfg = _cfg()
    params = _params(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 64))
    def ffn(lp, c):
        return jax.jit(lambda h, lp: _ffn(h, lp, c)[0])(h, lp)

    with jax.default_matmul_precision("highest"):
        y = ffn(lp, cfg)
        ref = jax.jit(lambda h, lp: qwen3next_expert_layer(h, lp, cfg))(
            h, lp)
        assert _err(y, ref) < TOL
        shut = dict(lp, shared_score=jnp.zeros_like(lp["shared_score"]))
        half = ffn(shut, cfg)
        plain = dataclasses.replace(cfg, shared_expert_gate=False)
        whole = ffn(lp, plain)
        routed = ffn(lp, dataclasses.replace(plain, n_shared_experts=0))
    np.testing.assert_allclose(half - routed, (whole - routed) / 2,
                               atol=1e-5)
    assert _err(y, whole) > 1e-2            # the gate is not a no-op


def test_four_shares_and_one_shared_expert_are_the_uncut_layer():
    """The share tied to the model: at a small size the routed parts of
    all four shares, the shared expert counted once, add up to the uncut
    reference's expert layer; so do the program's."""
    whole = _cfg(first_expert=0, n_experts_held=0)
    params = _params(whole)
    lp = jax.tree.map(lambda w: w[0], params["linear_layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 64))
    no_shared = dict(lp, shared_down=jnp.zeros_like(lp["shared_down"]))
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(
            lambda h, lp: qwen3next_expert_layer(h, lp, whole))
        ref = uncut(h, lp)
        shared = ref - uncut(h, no_shared)
        parts, parts_ref = shared, shared
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(whole, first_expert=first,
                                        n_experts_held=4)
            held = dict(no_shared, **{
                k: lp[k][first:first + 4]
                for k in ("moe_gate", "moe_up", "moe_down")})
            parts = parts + jax.jit(
                lambda h, lp: _ffn(h, lp, share)[0])(h, held)
            parts_ref = parts_ref + jax.jit(
                lambda h, lp: qwen3next_expert_layer(h, lp, share))(h, held)
    assert _err(parts_ref, ref) < TOL
    assert _err(parts, ref) < TOL
    assert float(jnp.max(jnp.abs(shared))) > 1e-3


def test_the_linear_stack_under_layer_plan_and_the_partition_rules():
    import re

    cfg = _cfg()
    plan = cfg.layer_plan()
    assert [(s.stack, s.index, s.mixer, s.rope) for s in plan] == [
        ("linear_layers", 0, "linear", False),
        ("linear_layers", 1, "linear", False),
        ("linear_layers", 2, "linear", False),
        ("layers", 0, "attention", True)]
    assert all(not s.dense_ffn and not s.window for s in plan)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "final_norm", "layers", "linear_layers",
                              "lm_head"]
    lin = params["linear_layers"]
    assert {k: v.shape[1:] for k, v in lin.items()
            if k.startswith("gdn_")} == {
        "gdn_norm": (64,), "gdn_in": (64, 2 * 16 + 2 * 64),
        "gdn_ba": (64, 8), "gdn_conv": (4, 2 * 16 + 64),
        "gdn_a_log": (4,), "gdn_dt_bias": (4,), "gdn_out_norm": (16,),
        "gdn_out": (64, 64)}
    assert all(v.shape[0] == 3 for v in lin.values())
    assert lin["shared_score"].shape == (3, 64, 1)
    assert not any(k.startswith(("w", "conv_")) for k in lin)
    assert not any(k.startswith("gdn_") for k in params["layers"])
    # every leaf of the new stack meets a rule, none a tensor axis
    rules = llama_partition_rules()
    for name, leaf in lin.items():
        spec = next(spec for pattern, spec in rules
                    if re.search(pattern, "linear_layers/" + name))
        assert len(spec) == leaf.ndim, name
        if name.startswith("gdn_"):
            assert "tensor" not in jax.tree.leaves(tuple(spec)), name
    # a dense model's leading linear layers would be a stack apart
    dense = _cfg(n_experts=0, n_shared_experts=0, shared_expert_gate=False,
                 first_expert=0, n_experts_held=0, moe_impl="auto")
    assert {s.stack for s in dense.layer_plan()} == {"linear_layers",
                                                     "layers"}


@pytest.mark.parametrize("bad,why", [
    (dict(linear_key_heads=0), "four sizes"),
    (dict(linear_value_dim=0), "four sizes"),
    (dict(conv_taps=0), "conv_taps come together"),
    (dict(linear_value_heads=3), "no multiple"),
    (dict(partial_rotary=5), "pairs"),
    (dict(partial_rotary=32), "pairs"),
    (dict(n_shared_experts=0), "gates a shared expert"),
    (dict(layer_types=(A, A, A, A)), "come together"),
])
def test_what_the_configuration_refuses(bad, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**bad)


def test_the_mixer_refuses_a_ragged_sequence_and_a_split_mesh():
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="chunks of 64"):
        llama_loss(params, _batch(cfg, (1, 96)), cfg)
    for split in ((2, 1), (1, 2)):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:2]).reshape(1, 1, *split),
            ("data", "fsdp", "seq", "tensor"))
        with pytest.raises(ValueError, match="runs whole on each device"):
            jax.eval_shape(lambda p: llama_loss(
                p, _batch(cfg, (2, 64)), cfg, mesh), params)


@pytest.mark.parametrize("field", [
    dict(layer_types=(L, L), conv_taps=4, linear_key_heads=2,
         linear_value_heads=4, linear_key_dim=8, linear_value_dim=8),
    dict(partial_rotary=8),
    dict(n_experts=4, n_shared_experts=1, shared_expert_gate=True)])
def test_decode_serving_and_the_pipeline_refuse_the_new_fields(field):
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field) - {"n_experts"}
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match="no pipeline schedule"):
        _validate_pipeline(cfg, 2, mesh, "seq", 2)
