"""Olmo-Hybrid-7B (olmo_hybrid) through the normal llama path against
the plain float32 reference (horovod_tpu/models/reference.py, whose
delta rule runs token by token): logits, loss and every gradient leaf
under each remat mode, for the published pattern (linear_attention x 3,
full_attention) at widths that keep the shape of the problem: keys and
values of TWO widths (12 and 24: the key head no multiple of the value
head's tile, three key heads odd against a tile of 8), write strengths
past 1 on the drawn batch, a dense SwiGLU beside the linear mixer, the
q/k norm over the whole projected width, no position encoding, and
every part normed on its OUTPUT alone: no input-norm leaf in the tree.
Also what the configuration, decode and the pipeline refuse. Small
sizes, CPU.

Tolerance: tests/single/test_qwen3next_reference.py's, for its reasons
(float32 on both sides; the chunked form's triangular solve and the
norms of small vectors in the linear layers: 5e-3 of the largest entry
there). The same comparison with the program in bfloat16 has to fail.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import llama_forward, llama_partition_rules
from horovod_tpu.models.reference import (
    olmohybrid_forward,
    olmohybrid_gated_delta_net,
    olmohybrid_loss,
    qwen3next_gated_delta_net,
)

GDN_TOL = 5e-3
L, A = "linear_attention", "full_attention"
STACKS = ("linear_layers", "layers")
INPUT_NORMS = ("attn_norm", "gdn_norm", "mlp_norm")


def _cfg(**kw):
    """The cell's shape in small: one period; three key heads of 12 and
    three value heads of 24; four attention heads on four; a dense FFN;
    output norms alone; an untied head."""
    base = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
                n_kv_heads=4, d_ff=96, norm_eps=1e-6,
                layer_types=(L, L, L, A), conv_taps=4, linear_key_heads=3,
                linear_value_heads=3, linear_key_dim=12,
                linear_value_dim=24, linear_beta_max=2.0, post_norm="only",
                qk_norm=True, dtype="float32", param_dtype="float32",
                remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    """Seeded weights with the norm gains drawn away from 1, so that a
    norm left out or misplaced moves the result."""
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
        return (jax.jit(lambda p: olmohybrid_forward(
                    p, batch["tokens"], cfg))(params),
                *jax.jit(jax.value_and_grad(
                    lambda p: olmohybrid_loss(p, batch, cfg)))(params))


def _program_readings(cfg):
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: llama_forward(
            p, batch["tokens"], cfg))(params)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg)))(params)
    return logits, loss, grads


def _leaf_errors(grads, ref):
    out = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        r = ref
        for key in path:
            r = r[key.key]
        assert float(jnp.max(jnp.abs(r))) > 0, path
        out[jax.tree_util.keystr(path)] = _err(g.astype(jnp.float32), r)
    return out


@pytest.mark.parametrize("remat", [False, "attn", "attn/ffn", True])
def test_logits_loss_and_every_gradient_leaf(remat):
    cfg = _cfg(remat=remat)
    ref_logits, ref_loss, ref = _reference_readings()
    logits, loss, grads = _program_readings(cfg)
    assert _err(logits, ref_logits) < GDN_TOL
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    errors = _leaf_errors(grads, ref)
    # 12 leaves a linear layer, 11 an attention layer, 3 at the ends
    assert len(errors) == 12 + 11 + 3
    assert max(errors.values()) < GDN_TOL, errors


def test_the_same_comparison_in_bfloat16_fails():
    """The limits tell the configuration's precision from float32's:
    the program computing in bfloat16 on the same float32 weights is
    refused, by the logits and by most gradient leaves."""
    ref_logits, _, ref = _reference_readings()
    logits, _, grads = _program_readings(_cfg(dtype="bfloat16"))
    assert _err(logits.astype(jnp.float32), ref_logits) > GDN_TOL
    errors = _leaf_errors(grads, ref)
    assert sum(e > GDN_TOL for e in errors.values()) > len(errors) // 2


def test_the_tree_has_no_input_norm_and_the_batch_writes_past_one():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    assert sorted(params) == ["embed", "final_norm", "layers",
                              "linear_layers", "lm_head"]
    for stack in STACKS:
        assert not set(params[stack]) & set(INPUT_NORMS), stack
        assert {"post_attn_norm", "post_mlp_norm", "w_gate", "w_up",
                "w_down"} <= set(params[stack]), stack
    assert params["layers"]["q_norm"].shape == (1, 64)       # whole width
    lin = params["linear_layers"]
    assert {k: v.shape[1:] for k, v in lin.items()
            if k.startswith("gdn_")} == {
        "gdn_in": (64, 2 * 36 + 2 * 72), "gdn_ba": (64, 6),
        "gdn_conv": (4, 2 * 36 + 72), "gdn_a_log": (3,),
        "gdn_dt_bias": (3,), "gdn_out_norm": (24,), "gdn_out": (72, 64)}
    # the first layer's write strengths on the drawn batch: past 1, and
    # under 2
    h = params["embed"][batch["tokens"]]
    beta = 2.0 * jax.nn.sigmoid((h @ lin["gdn_ba"][0])[..., :3])
    assert 1.0 < float(jnp.max(beta)) < 2.0 and float(jnp.min(beta)) < 1.0
    # every leaf meets a partition rule of its own rank
    import re

    rules = llama_partition_rules()
    for stack in STACKS:
        for name, leaf in params[stack].items():
            spec = next(spec for pattern, spec in rules
                        if re.search(pattern, f"{stack}/{name}"))
            assert len(spec) == leaf.ndim, name


def test_the_write_strength_is_the_one_line_that_changes():
    """``linear_beta_max`` 2 doubles beta and nothing else: at 1 the
    reference's mixer IS Qwen3-Next's, at 2 it is not, and the program
    follows both."""
    from horovod_tpu.models.llama import _gated_delta_net

    cfg = _cfg()
    params = _params(cfg)
    lp = jax.tree.map(lambda w: w[0], params["linear_layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    one = dataclasses.replace(cfg, linear_beta_max=1.0)
    with jax.default_matmul_precision("highest"):
        ref2 = jax.jit(lambda h: olmohybrid_gated_delta_net(h, lp, cfg))(h)
        ref1 = jax.jit(lambda h: olmohybrid_gated_delta_net(h, lp, one))(h)
        qwen = jax.jit(lambda h: qwen3next_gated_delta_net(h, lp, one))(h)
        got2 = jax.jit(lambda h: _gated_delta_net(h, lp, cfg, None, None))(h)
        got1 = jax.jit(lambda h: _gated_delta_net(h, lp, one, None, None))(h)
    np.testing.assert_array_equal(ref1, qwen)
    assert _err(ref2, ref1) > 0.05
    assert _err(got2, ref2) < GDN_TOL and _err(got1, ref1) < GDN_TOL


def test_each_output_norm_moves_the_result_and_no_input_is_normed():
    """A gain of an output norm changed moves the logits; scaling the
    stream a part reads by 2 is NOT undone (an input norm would undo
    it): the mixer's output under its norm is scale-free, its input is
    not."""
    cfg = _cfg(n_layers=2, layer_types=(L, A))
    params, batch = _params(cfg), _batch(cfg, (1, 64))
    forward = jax.jit(lambda p: llama_forward(p, batch["tokens"], cfg))
    base = forward(params)
    for stack in STACKS:
        for name in ("post_attn_norm", "post_mlp_norm"):
            moved = jax.tree.map(lambda w: w, params)
            moved[stack][name] = params[stack][name] * 1.5
            assert _err(forward(moved), base) > 1e-3, (stack, name)
    # the embedding doubled: a pre-norm model's first mixer would see the
    # same input; here the recurrence's gates see twice the stream
    doubled = dict(params, embed=params["embed"] * 2.0)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p: olmohybrid_forward(
            p, batch["tokens"], cfg))(doubled)
    assert _err(forward(doubled), ref) < GDN_TOL
    assert _err(forward(doubled), base) > 1e-2


@pytest.mark.parametrize("bad,why", [
    (dict(linear_beta_max=2.5), "in \\(0, 2\\]"),
    (dict(linear_beta_max=0.0), "in \\(0, 2\\]"),
    (dict(layer_types=(A, A, A, A), conv_taps=0, linear_key_heads=0,
          linear_value_heads=0, linear_key_dim=0, linear_value_dim=0),
     "none without such a layer"),
    (dict(post_norm="both"), "unknown post_norm"),
    (dict(hc_mult=4, hc_sinkhorn_iters=2, hc_eps=1e-6,
          hc_clamp=(-30.0, 30.0)), "hyper-"),
    (dict(layer_types=("conv", L, L, A)), "norm\\s+their input inside"),
    (dict(layer_types=(A, "experts", A, "experts"), one_part_layers=True,
          n_experts=4, conv_taps=0, linear_key_heads=0,
          linear_value_heads=0, linear_key_dim=0, linear_value_dim=0,
          linear_beta_max=1.0), "layer of one part"),
])
def test_what_the_configuration_refuses(bad, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**bad)


def test_output_only_norms_refuse_latent_attention():
    with pytest.raises(ValueError, match="latent attention"):
        LlamaConfig.tiny(post_norm="only", q_lora_rank=8, kv_lora_rank=8,
                         qk_nope_head_dim=8, qk_rope_head_dim=8,
                         v_head_dim=8)


@pytest.mark.parametrize("field,decode,stage", [
    (dict(post_norm="only"), "attn_norm and mlp_norm",
     "norm each part's INPUT"),
    (dict(layer_types=(L, L), conv_taps=4, linear_key_heads=2,
          linear_value_heads=2, linear_key_dim=8, linear_value_dim=16,
          linear_beta_max=2.0), "cache of its recurrent state",
     "linear_beta_max")])
def test_decode_serving_and_the_pipeline_refuse_the_new_fields(
        field, decode, stage):
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match=decode):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match=stage):
        _validate_pipeline(cfg, 2, mesh, "seq", 2)


def test_the_four_norm_mode_and_the_default_are_what_they_were():
    """``post_norm`` False and True keep their leaves: the new value
    takes nothing from the old two."""
    plain = llama_init(LlamaConfig.tiny(), jax.random.PRNGKey(0))
    assert {"attn_norm", "mlp_norm"} <= set(plain["layers"])
    assert not {"post_attn_norm", "post_mlp_norm"} & set(plain["layers"])
    four = llama_init(LlamaConfig.tiny(post_norm=True),
                      jax.random.PRNGKey(0))
    assert {"attn_norm", "mlp_norm", "post_attn_norm",
            "post_mlp_norm"} <= set(four["layers"])
    assert not LlamaConfig.tiny().training_only_fields()
