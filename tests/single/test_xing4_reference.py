"""Xing4.0-29B-A4B's layers through the program's normal path
(``LlamaConfig`` -> ``llama_init`` -> ``llama_loss``) against the plain
float32 reference (``horovod_tpu/models/reference.py: xing4_*``) on seeded
weights, at small sizes with the published RATIOS on the CPU (queries and
keys one and a half times the values' width, a rotated slice shared by all
heads, four streams, twenty Sinkhorn iterations, four of eight experts a
token with four held): the loss and every gradient leaf under each remat
mode; that each of nine planted faults is refused; the hyper-connections
alone; that the eight shares add up to the uncut expert layer; what the
configuration, decode, serving, the pipeline and the sequence axes refuse;
and that a configuration that sets none of the new fields builds the tree
it always did.

Float32 compute: program and reference then differ in the order of
float32 additions (the blocks of the head, the sorted rows, a sum over
streams against an einsum): 5e-6 of a loss, 1e-5 (l2) of a gradient leaf
through two layers and the MTP module's. The tolerances stand ten times
off that, and ten times under what the mildest planted fault reads
(nineteen iterations for twenty). A compile of the whole step is most of
what a test here costs (about 15 s): the program and the reference are
compiled once a configuration, and two remat modes and three of the nine
planted faults are marked ``slow``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models import llama
from horovod_tpu.models import reference as ref

pytestmark = pytest.mark.quick
F32 = jnp.float32
TOL, GRAD_TOL = 5e-5, 1e-4
# Rows and columns of ``H_res`` against 1 after the published twenty
# iterations, on logits like the seed's (``2 I`` plus unit noise), a
# token's worst row or column: the MEAN over tokens and the WORST token.
# Sinkhorn-Knopp converges linearly, at a rate the token's logits set
# (the last half iteration leaves the columns exact and the rows where
# they were): 1.6e-4 / 0.016 over 4096 tokens and 1.3e-4 / 0.027 over
# 65,536 after twenty, 0.085 / 0.38 after two.
HC_SUM_TOL = {"mean": 1e-3, "worst": 5e-2}


def _cfg(**kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, rope_theta=10000.0, norm_eps=1e-6, n_dense_layers=1,
        n_experts=8, n_experts_held=4, n_experts_per_token=4, moe_d_ff=16,
        n_shared_experts=1, score_func="sigmoid", route_scale=2.0,
        moe_impl="grouped", moe_aux_weight=0.0, q_lora_rank=12,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0),
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-30.0, 30.0), mtp_layers=1,
        mtp_types=("full_attention",), mtp_weight=0.1, dtype="float32",
        remat="attn/ffn", loss_chunk=8)
    base.update(kw)
    return LlamaConfig(**base)


def _batch(cfg, shape=(2, 16)):
    tokens = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _params(cfg):
    """Seeded weights with every gain, scalar and bias moved off its
    start, so that a gain left out or a bias misplaced shows."""
    params = llama_init(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 256))

    def moved(path, w):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            return 0.1 * jax.random.normal(next(keys), w.shape, w.dtype)
        if "norm" in name or "alpha" in name or "hc_" in name \
                and "bias" in name:
            w = w + 0.2 * jax.random.normal(next(keys), w.shape, w.dtype)
        if "alpha" in name:
            # logits of H_res several units apart: Sinkhorn-Knopp then
            # converges slowly enough for its twentieth iteration to show
            w = w.at[..., 2].set(4.0)
        return w

    return jax.tree_util.tree_map_with_path(moved, params)


def _loss_and_grads(f, params):
    return jax.jit(jax.value_and_grad(f))(params)


# A program a configuration, compiled once a process (a compile of the
# whole step is most of what a test here costs).
@functools.lru_cache(maxsize=None)
def _program(cfg):
    return jax.jit(jax.value_and_grad(
        lambda p, b: llama_loss(p, b, cfg)))


@functools.lru_cache(maxsize=None)
def _reference(cfg):
    return jax.jit(jax.value_and_grad(
        lambda p, b: ref.xing4_loss(p, b, cfg)))


def _worst_leaf(got, want):
    errs = jax.tree_util.tree_leaves_with_path(jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w)
                           / (jnp.linalg.norm(w) + 1e-30)), got, want))
    return max((e, jax.tree_util.keystr(p)) for p, e in errs)


def _off(cfg, params, batch, want, want_grads, patched=False):
    """How far the program on ``cfg`` stands from the reference's loss
    and gradients, in tolerances. ``patched``: the program's functions
    were replaced, so no compiled program of ``cfg`` is this one."""
    loss, grads = _loss_and_grads(
        lambda p: llama_loss(p, batch, cfg), params) if patched \
        else _program(cfg)(params, batch)
    return max(abs(float(loss) - float(want)) / float(want) / TOL,
               _worst_leaf(grads, want_grads)[0] / GRAD_TOL)


@pytest.fixture(scope="module")
def the_model():
    cfg = _cfg()
    return cfg, _params(cfg), _batch(cfg)


@pytest.fixture(scope="module")
def the_reference(the_model):
    """The reference's loss and every gradient leaf beside the model:
    for the cases that compare with them, and no other (fifteen seconds
    wherever a worker first asks)."""
    cfg, params, batch = the_model
    want, want_grads = _reference(cfg)(params, batch)
    return cfg, params, batch, want, want_grads


slow = pytest.mark.slow      # a compile of the whole step each, ~15 s


@pytest.mark.parametrize("remat", [
    "attn/ffn", "attn", pytest.param(True, marks=slow),
    pytest.param(False, marks=slow)])
def test_loss_and_every_gradient_leaf_against_the_reference(the_reference,
                                                            remat):
    cfg, params, batch, want, want_grads = the_reference
    cfg = dataclasses.replace(cfg, remat=remat)
    loss, grads = _program(cfg)(params, batch)
    assert abs(float(loss) - float(want)) <= TOL * float(want)
    # no gradient reaches the selection bias, on either side
    assert not np.any(np.asarray(grads["layers"]["expert_bias"]))
    worst = _worst_leaf(grads, want_grads)
    assert worst[0] <= GRAD_TOL, worst


def test_the_seam_on_its_kernels_against_the_reference(monkeypatch):
    """Heads 128 + 64 beside 128 wide, the widths at which latent
    attention's seam runs as ``ops/mla_prep.py``'s kernel pair (in
    interpret mode here), under the cell's remat mode: the logits, the
    loss and every gradient leaf at the limits of the small model. The
    forward traces two seams (the dense layer's and the expert stack's),
    the step a third (the MTP module's)."""
    from horovod_tpu.ops import mla_prep

    calls, plain = [], mla_prep.mla_prep

    def counted(yq, *rest):
        calls.append(yq.shape)
        return plain(yq, *rest)

    monkeypatch.setattr(mla_prep, "_INTERPRET", True)
    monkeypatch.setattr(mla_prep, "mla_prep", counted)
    cfg = _cfg(n_heads=2, n_kv_heads=2, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, remat="attn")
    params, batch = _params(cfg), _batch(cfg)
    logits = jax.jit(lambda p: llama.llama_forward(p, batch["tokens"],
                                                   cfg))(params)
    want = jax.jit(lambda p: ref.xing4_forward(p, batch["tokens"],
                                               cfg))(params)
    assert calls == [(2, 16, 2 * 192)] * 2, calls
    assert float(jnp.linalg.norm(logits - want)
                 / jnp.linalg.norm(want)) <= GRAD_TOL
    want, want_grads = _reference(cfg)(params, batch)
    assert _off(cfg, params, batch, want, want_grads) <= 1
    assert len(calls) == 2 + 3


def test_the_two_terms(the_model):
    cfg, params, batch = the_model
    main, mtp = jax.jit(lambda p: ref.xing4_loss(p, batch, cfg,
                                                 terms=True))(params)
    both = jax.jit(lambda p: llama_loss(p, batch, cfg))(params)
    assert abs(float(both) - float(main + cfg.mtp_weight * mtp)) \
        <= TOL * float(both)
    assert float(mtp) > 0


# -- planted faults: the PROGRAM with one departure, against the reference


def _nineteen_iterations(cfg, monkeypatch):
    return dataclasses.replace(cfg, hc_sinkhorn_iters=19)


def _no_m_squared_in_the_scale(cfg, monkeypatch):
    """``1 / sqrt(d_qk)`` without ``m^2`` (the frequencies as they
    were: ``mscale`` and ``mscale_all_dim`` 0 leave cos and sin at 1)."""
    return dataclasses.replace(cfg, rope_yarn=cfg.rope_yarn[:4] + (0.0, 0.0))


def _no_yarn_in_the_frequencies(cfg, monkeypatch):
    monkeypatch.setattr(
        LlamaConfig, "yarn", lambda c: (
            (c.rope_theta ** (-np.arange(0, c.qk_rope_head_dim, 2)
                              / c.qk_rope_head_dim)).astype(np.float32),
            1.0, ref.xing4_yarn(c)[2]))
    return cfg


def _h_post_without_its_two(cfg, monkeypatch):
    monkeypatch.setattr(llama, "_HC_POST_SCALE", 1.0)
    return cfg


def _a_rotated_key_a_head(cfg, monkeypatch):
    """Every head its own ``k_r`` (here: the shared one rolled by the
    head's number) where the model has ONE for all heads."""
    monkeypatch.setattr(
        llama, "_one_key_for_all_heads", lambda k_r, heads: jnp.concatenate(
            [jnp.roll(k_r, h, -1) for h in range(heads)], 2))
    return cfg


def _sinkhorn_in_bf16(cfg, monkeypatch):
    plain = llama._sinkhorn
    monkeypatch.setattr(
        llama, "_sinkhorn", lambda logits, *a: plain(
            logits.astype(jnp.bfloat16), *a).astype(F32))
    return cfg


def _router_in_bf16(cfg, monkeypatch):
    plain = llama.moe_route
    monkeypatch.setattr(
        llama, "moe_route", lambda h, w, *a, **k: plain(
            h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), *a, **k))
    return cfg


def _streams_not_summed_into_the_mtp_module(cfg, monkeypatch):
    """The MTP module reads a quarter of the sum (a mean of the
    streams): its hidden norm has a gain, so the scale shows."""
    plain = llama._mtp_hidden
    monkeypatch.setattr(
        llama, "_mtp_hidden", lambda params, stream, *a: plain(
            params, stream * 0.25 + 0.01, *a))
    return cfg


def _rms_a_stream_and_not_over_all(cfg, monkeypatch):
    """The coefficients from streams normed one by one (over ``d``)
    where the model norms all ``n d`` values together."""
    plain = llama._hc_coefficients

    def normed(X, *a):
        Xf = X.astype(F32)
        return plain(Xf * jax.lax.rsqrt(jnp.mean(Xf * Xf, -1,
                                                 keepdims=True)), *a)

    monkeypatch.setattr(llama, "_hc_coefficients", normed)
    return cfg


@pytest.mark.parametrize("plant", [
    _nineteen_iterations, _no_m_squared_in_the_scale,
    _h_post_without_its_two, _a_rotated_key_a_head, _sinkhorn_in_bf16,
    _router_in_bf16,
    pytest.param(_no_yarn_in_the_frequencies, marks=slow),
    pytest.param(_streams_not_summed_into_the_mtp_module, marks=slow),
    pytest.param(_rms_a_stream_and_not_over_all, marks=slow)])
def test_a_planted_fault_is_refused(the_reference, monkeypatch, plant):
    """The program with one fault planted no longer agrees with the
    reference: the loss or a gradient leaf stands at least ten
    tolerances off (the program as it is: under one,
    ``test_loss_and_every_gradient_leaf_against_the_reference``)."""
    cfg, params, batch, want, want_grads = the_reference
    off = _off(plant(cfg, monkeypatch), params, batch, want, want_grads,
               patched=True)
    assert off > 10, off


def test_a_missing_clamp_is_refused(the_model):
    """Logits of 40 in ``H_res`` (a bias moved there): the reference
    clamps them to 30 before ``exp``, and a program that does not
    stands off."""
    cfg, params, batch = the_model
    bias = params["layers"]["hc_attn_bias"].at[:, 2 * cfg.hc_mult].set(40.0)
    params = {**params, "layers": {**params["layers"],
                                   "hc_attn_bias": bias}}
    want, want_grads = _reference(cfg)(params, batch)
    assert _off(cfg, params, batch, want, want_grads) <= 1
    loose = dataclasses.replace(cfg, hc_clamp=(-1e4, 1e4))
    assert _off(loose, params, batch, want, want_grads) > 10


# -- the hyper-connections alone


def _seed_like_logits(n=4, tokens=4096):
    noise = jax.random.normal(jax.random.PRNGKey(3), (n, n, tokens), F32)
    return noise + 2.0 * jnp.eye(n)[:, :, None]


def _off_one(m):
    """A token's worst row or column sum against 1 -> (the mean over
    tokens, the worst token)."""
    off = jnp.maximum(jnp.abs(m.sum(0) - 1.0).max(0),
                      jnp.abs(m.sum(1) - 1.0).max(0))
    return {"mean": float(off.mean()), "worst": float(off.max())}


def test_h_res_is_doubly_stochastic_after_twenty_iterations():
    """Rows and columns sum to 1 within ``HC_SUM_TOL`` after the
    published 20 iterations and do not after 2, by either statistic;
    every entry is a share."""
    logits = _seed_like_logits()
    done = llama._sinkhorn(logits, 20, 1e-6, (-30.0, 30.0))
    begun = llama._sinkhorn(logits, 2, 1e-6, (-30.0, 30.0))
    for stat, tol in HC_SUM_TOL.items():
        assert _off_one(done)[stat] <= tol < _off_one(begun)[stat], \
            (stat, _off_one(done), _off_one(begun))
    assert float(done.min()) >= 0 and float(done.max()) <= 1.0 + 1e-6


@pytest.mark.parametrize("logits", [
    _seed_like_logits(tokens=256),
    _seed_like_logits(tokens=256).at[0, 1].set(40.0)],
    ids=["seed-like", "an-entry-past-the-clamp"])
def test_the_iterations_against_the_reference(logits):
    """``_sinkhorn`` with the tokens on the lanes against the
    reference's [tokens, n, n]; an entry of 40 is read as 30."""
    done = llama._sinkhorn(logits, 20, 1e-6, (-30.0, 30.0))
    want = ref.xing4_sinkhorn(jnp.moveaxis(logits, 2, 0), _cfg())
    np.testing.assert_allclose(jnp.moveaxis(done, 2, 0), want, rtol=1e-5,
                               atol=1e-7)
    at_the_clamp = llama._sinkhorn(jnp.minimum(logits, 30.0), 20, 1e-6,
                                   (-30.0, 30.0))
    np.testing.assert_array_equal(done, at_the_clamp)


def test_the_coefficients_against_the_reference(the_model):
    """``_hc_coefficients`` in the program's layout [B, n, T, D] against
    the reference's [B, T, n, D]; at the seed ``H_res`` is not the
    identity and ``H_pre``, ``H_post`` differ by stream and token."""
    cfg, params, _ = the_model
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    X = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.hc_mult,
                                                  cfg.d_model), F32)
    pre, post, res = jax.jit(lambda X: llama._hc_coefficients(
        jnp.swapaxes(X, 1, 2), lp["hc_mlp_phi"], lp["hc_mlp_alpha"],
        lp["hc_mlp_bias"], cfg))(X)
    want = jax.jit(lambda X: ref.xing4_hc_coefficients(X, lp, "mlp",
                                                       cfg))(X)
    for got, w in zip((jnp.swapaxes(pre, 1, 2), jnp.swapaxes(post, 1, 2),
                       jnp.moveaxis(res, 3, 1)), want):
        np.testing.assert_allclose(got, w, rtol=2e-5, atol=2e-6)
    seed = llama_init(cfg, jax.random.PRNGKey(0))["layers"]
    pre, post, res = llama._hc_coefficients(
        jnp.swapaxes(X, 1, 2), seed["hc_mlp_phi"][0],
        seed["hc_mlp_alpha"][0], seed["hc_mlp_bias"][0], cfg)
    assert float(jnp.abs(res - jnp.eye(4)[None, :, :, None]).max()) > 0.3
    assert float(jnp.std(pre, 1).min()) > 1e-3 \
        and float(jnp.std(post, 1).min()) > 1e-3
    assert float(post.max()) > 1.0          # the factor 2


# -- the share tied to the model


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the two shares of four experts (the cell:
    eight of eight) plus the shared expert ONCE are the uncut
    reference's expert layer; and the program's FFN on a share is the
    reference's on that share."""
    cfg = _cfg(n_experts_held=0, mtp_layers=0, mtp_types=(),
               mtp_weight=0.0)
    lp = jax.tree.map(lambda w: w[0], _params(cfg)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model), F32)
    with jax.default_matmul_precision("highest"):
        shared, routed = jax.jit(
            lambda h, lp: ref.xing4_expert_layer(h, lp, cfg))(h, lp)
        total = 0.0
        for first in (0, 4):
            share = dataclasses.replace(cfg, first_expert=first,
                                        n_experts_held=4)
            held = {k: (w[first:first + 4] if k.startswith("moe_") else w)
                    for k, w in lp.items()}
            once, part = jax.jit(lambda h, lp, share=share:
                                 ref.xing4_expert_layer(h, lp, share))(
                h, held)
            np.testing.assert_allclose(once, shared, rtol=1e-6)
            total = total + part
            got = jax.jit(lambda h, lp, share=share:
                          llama._ffn(h, lp, share)[0])(h, held)
            np.testing.assert_allclose(got, once + part, rtol=2e-4,
                                       atol=2e-6)
    np.testing.assert_allclose(total, routed, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(routed).max()) > 0


def test_the_parameter_tree():
    """Latent attention's seven leaves and a part's three
    hyper-connection leaves a layer, the MTP module an EXPERT layer of
    its own whatever the model's leading dense layers."""
    cfg = _cfg()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"dense_layers", "layers", "embed", "final_norm",
                           "lm_head", "mtp"}
    mla = {"attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
           "wkv_b", "wo", "mlp_norm"}
    hc = {f"hc_{p}_{leaf}" for p in ("attn", "mlp")
          for leaf in ("phi", "alpha", "bias")}
    assert set(params["dense_layers"]) == mla | hc | {"w_gate", "w_up",
                                                      "w_down"}
    experts = {"router", "expert_bias", "moe_gate", "moe_up", "moe_down",
               "shared_gate", "shared_up", "shared_down"}
    assert set(params["layers"]) == mla | hc | experts
    assert set(params["mtp"]["layers"]) == mla | hc | experts
    assert params["layers"]["wq_b"].shape == (1, 12, 4 * 12)
    assert params["layers"]["wkv_a"].shape == (1, 32, 8 + 4)
    assert params["layers"]["wkv_b"].shape == (1, 8, 4 * 16)
    assert params["layers"]["wo"].shape == (1, 4 * 8, 32)
    assert params["layers"]["hc_attn_phi"].shape == (1, 4, 32, 24)
    assert params["layers"]["hc_attn_alpha"].dtype == F32
    plan = cfg.layer_plan(mtp=True)
    assert [(s.stack, s.dense_ffn) for s in plan] == [("layers", False)]
    # every leaf has a partition rule of its own kind
    import re
    for path, w in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rule = next(spec for pat, spec in llama.llama_partition_rules()
                    if re.search(pat, name))
        assert len(rule) == w.ndim, (name, rule)


# -- what refuses


MLA = dict(q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8)
HC = dict(hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
          hc_clamp=(-30.0, 30.0))


@pytest.mark.parametrize("field, lacks", [
    (MLA, "cache of the latent"),
    ({**MLA, "rope_yarn": (64.0, 16.0, 32.0, 1.0, 1.0, 1.0)},
     "absorbed form"),
    (HC, "ONE residual stream")], ids=["latent", "yarn", "streams"])
def test_decode_serving_and_the_pipeline_refuse_the_new_fields(field,
                                                               lacks):
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="training only") as said:
        gen.llama_prefill(params, prompt, cfg)
    assert lacks in str(said.value)
    assert all(f in str(said.value) for f in field)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError,
                       match="(kv_lora_rank|hc_mult).*no pipeline"):
        _validate_pipeline(cfg, 2, mesh, "seq", 2)


def test_latent_attention_refuses_a_sequence_axis():
    cfg = LlamaConfig.tiny(dtype="float32", **MLA)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:2]).reshape(1, 1, 2, 1),
        ("data", "fsdp", "seq", "tensor"))
    with pytest.raises(ValueError, match="no sequence-parallel"):
        jax.eval_shape(lambda p: llama_loss(
            p, _batch(cfg, (2, 64)), cfg, mesh), params)


@pytest.mark.parametrize("bad, match", [
    (dict(kv_lora_rank=8), "five sizes"),
    ({**MLA, "qk_rope_head_dim": 3}, "in pairs"),
    ({**MLA, "rope_yarn": (64.0, 16.0)}, "six numbers"),
    (dict(rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0)), "six numbers"),
    ({**MLA, "qk_norm": True}, "no qk_norm"),
    ({**MLA, "attn_gate": True}, "no qk_norm"),
    (dict(hc_mult=4), "come together"),
    ({**HC, "hc_mult": 1}, "come together"),
    ({**HC, "hc_clamp": (30.0,)}, "come together")])
def test_the_configuration_refuses(bad, match):
    with pytest.raises(ValueError, match=match):
        LlamaConfig.tiny(**bad)


def test_a_configuration_without_the_new_fields_builds_what_it_built():
    """The leaves, their shapes and their VALUES at the seed are those of
    the tree before this file's fields (a hash the parent commit gives),
    and no field is training only."""
    import hashlib

    cfg = LlamaConfig.tiny_moe(n_dense_layers=1, n_layers=3, moe_d_ff=32,
                               n_shared_experts=1, score_func="sigmoid",
                               mtp_layers=1, mtp_types=("full_attention",),
                               mtp_weight=0.1, dtype="float32")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    # the MTP module of a model with leading dense layers: before this
    # PR its first layers were dense too (no configuration had both)
    assert "router" in params["mtp"]["layers"]
    plain = LlamaConfig.tiny(dtype="float32")
    assert not plain.training_only_fields()
    h = hashlib.sha256()
    for path, w in jax.tree_util.tree_leaves_with_path(
            llama_init(plain, jax.random.PRNGKey(0))):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(w).tobytes())
    assert h.hexdigest() == PLAIN_TREE, h.hexdigest()


PLAIN_TREE = (
    "e5348b0356b43363fc7cc28f85ec385878c4246dcdf724d5bb835ac0afc3fefa")
