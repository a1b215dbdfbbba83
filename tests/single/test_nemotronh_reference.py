"""NVIDIA-Nemotron-3-Super-120B-A12B's layers through the program's
normal path (``LlamaConfig`` -> ``llama_init`` -> ``llama_loss``) against
the plain float32 reference (``horovod_tpu/models/reference.py:
nemotronh_*``) on seeded weights, at small sizes on the CPU: the loss and
every gradient leaf of one period and its MTP module, with and without
the MTP term, whole and as a share of the experts; that the shares add up
to the uncut expert layer; that a one-part layer plan gives each stack
its own leaves only; that each of four planted faults is refused; what
the configuration, decode, serving, the pipeline and the sequence axes
refuse; and that a configuration that sets none of the new fields builds
the tree it always did.

Float32 compute: program and reference then differ in the order of
float32 additions (the recurrence's chunks, the blocks of the head, the
sorted rows): 2e-5 of a loss, 5e-5 (l2) of a gradient leaf through
eleven layers."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
)
from horovod_tpu.models import generate as gen
from horovod_tpu.models import reference as ref
from horovod_tpu.models.llama import _ffn, llama_partition_rules

pytestmark = pytest.mark.quick
F32 = jnp.float32
M, A, E = "mamba2", "full_attention", "experts"
TOL, GRAD_TOL = 2e-5, 5e-5
PERIOD = (M, E, M, E, M, E, M, E, M, A, E)
SSD = dict(conv_taps=4, mamba_conv_bias=True, ssd_heads=8, ssd_head_dim=16,
           ssd_state=32, ssd_groups=2, ssd_chunk=16)
EXPERTS = dict(n_experts=16, n_experts_per_token=3, moe_d_ff=48,
               n_shared_experts=1, shared_d_ff=96, moe_latent=32,
               ffn_act="relu2", score_func="sigmoid", route_scale=5.0,
               moe_aux_weight=0.0, moe_impl="grouped")
MTP = dict(mtp_layers=1, mtp_types=(A, E), mtp_weight=0.1)


def _cfg(layer_types=PERIOD, mtp=True, **kw):
    """The cell's shape in small: one period of one-part layers (five
    SSD mixers, five latent expert layers, one attention layer of four
    heads on two) and the MTP module."""
    base = dict(vocab_size=128, d_model=64, n_layers=len(layer_types),
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=48,
                one_part_layers=True, layer_types=layer_types,
                dtype="float32", remat="attn",
                **(SSD if M in layer_types else {}), **EXPERTS,
                **(MTP if mtp else {}))
    base.update(kw)
    return LlamaConfig.tiny(**base)


def _params(cfg, seed=0):
    """Seeded weights with every gain, bias and skip moved off its
    start, so that a dropped or misplaced one shows."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))
    return jax.tree.map(
        lambda w: w + 0.05 * jax.random.normal(next(keys), w.shape, w.dtype)
        if w.ndim <= 2 and w.dtype == F32 else w, params)


def _batch(cfg, shape=(2, 64), seed=2):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _loss_and_grads(loss, params):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


def _worst_leaf(got, want):
    errs = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w)
                           / (jnp.linalg.norm(w) + 1e-30)), got, want)
    path, err = max(jax.tree_util.tree_leaves_with_path(errs),
                    key=lambda kv: kv[1])
    return err, jax.tree_util.keystr(path)


@pytest.mark.parametrize("case", [
    dict(layer_types=(M, E, A)),                     # both terms
    dict(layer_types=(M, E, A), mtp=False),          # the main term alone
    # a whole period, a share of the experts, the heads in blocks
    dict(n_experts_held=4, first_expert=8, loss_chunk=32),
    dict(layer_types=(M, E, A), mtp=False, remat="attn/ffn")],
    ids=["mtp", "no-mtp", "share-in-blocks", "attn/ffn"])
def test_loss_and_every_gradient_leaf_against_the_reference(case):
    cfg = _cfg(**case)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = _loss_and_grads(lambda p: llama_loss(p, batch, cfg),
                                  params)
    want, want_grads = _loss_and_grads(
        lambda p: ref.nemotronh_loss(p, batch, cfg), params)
    assert abs(float(loss) - float(want)) < TOL * float(want)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    err, where = _worst_leaf(grads, want_grads)
    assert err < GRAD_TOL, (where, err)
    # every leaf but the routers' selection biases is reached
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        reached = float(jnp.linalg.norm(g)) > 0
        assert reached != ("expert_bias" in jax.tree_util.keystr(path))


def test_the_two_terms_and_the_logits():
    """``llama_forward`` returns the main logits only; the loss is the
    main term plus ``mtp_weight`` times the module's, whose last position
    a sequence is masked."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        # one program a side: the model runs once in each
        logits, loss, alone = jax.jit(lambda p: (
            llama_forward(p, batch["tokens"], cfg),
            llama_loss(p, batch, cfg),
            llama_loss(p, batch, dataclasses.replace(cfg, mtp_weight=1.0))
        ))(params)
        want, (main, mtp) = jax.jit(lambda p: (
            ref.nemotronh_forward(p, batch["tokens"], cfg,
                                  batch["targets"])[0],
            ref.nemotronh_loss(p, batch, cfg, terms=True)))(params)
    assert float(jnp.max(jnp.abs(logits - want))
                 / jnp.max(jnp.abs(want))) < TOL
    assert abs(float(loss) - float(main + 0.1 * mtp)) < TOL * float(loss)
    assert abs(float(alone - main) - float(mtp)) < 10 * TOL * float(mtp)
    assert float(mtp) > 1.0


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixty-four shares of two experts of 128: the held experts' parts
    after the up-projection, with the router, the latent projections and
    the shared expert counted once, are the uncut layer's output: the
    PROGRAM's expert layer a share at a time against the reference's
    uncut one."""
    cfg = _cfg((E,), mtp=False, n_experts=128, n_experts_per_token=22)
    lp = jax.tree.map(lambda w: w[0],
                      _params(cfg)["expert_layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        shared, routed = ref.nemotronh_expert_layer(h, lp, cfg)

        @jax.jit
        def share(first):
            """The program's layer for experts ``first``, ``first + 1``,
            less the shared expert's part."""
            held = {k: jax.lax.dynamic_slice_in_dim(lp[k], first, 2)
                    for k in ("moe_up", "moe_down")}
            c = dataclasses.replace(cfg, n_experts_held=2)
            # the share's experts moved to the front of the router, so
            # that one program serves every share
            order = (jnp.arange(128) + first) % 128
            moved = {"router": lp["router"][:, order],
                     "expert_bias": lp["expert_bias"][order]}
            return _ffn(h, {**lp, **held, **moved}, c)[0] - shared

        parts = [share(first) for first in range(0, 128, 2)]
    assert float(jnp.max(jnp.abs(sum(parts) - routed))
                 / jnp.max(jnp.abs(routed))) < TOL
    # and no share is the whole: the largest part is a fraction of it
    assert max(float(jnp.linalg.norm(p)) for p in parts) \
        < 0.5 * float(jnp.linalg.norm(routed))


def test_a_one_part_layer_plan_gives_each_stack_its_own_leaves():
    cfg = _cfg()
    plan = cfg.layer_plan()
    assert [(s.stack, s.index, s.mixer, s.dense_ffn, s.rope)
            for s in plan[-3:]] == [
        ("mamba2_layers", 4, "mamba2", None, False),
        ("layers", 0, "attention", None, False),
        ("expert_layers", 4, None, False, False)]
    assert [(s.stack, s.index) for s in cfg.layer_plan(mtp=True)] == [
        ("layers", 0), ("expert_layers", 0)]
    shapes = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "expert_layers", "final_norm",
                              "layers", "lm_head", "mamba2_layers", "mtp"]
    attention = ["attn_norm", "wk", "wo", "wq", "wv"]
    experts = ["expert_bias", "mlp_norm", "moe_down", "moe_lat_down",
               "moe_lat_up", "moe_up", "router", "shared_down",
               "shared_up"]
    assert sorted(shapes["layers"]) == attention
    assert sorted(shapes["expert_layers"]) == experts
    assert sorted(shapes["mamba2_layers"]) == [
        "ssd_a_log", "ssd_conv", "ssd_conv_bias", "ssd_d", "ssd_dt_bias",
        "ssd_in", "ssd_norm", "ssd_out", "ssd_out_norm"]
    assert sorted(shapes["mtp"]) == [
        "eh_proj", "expert_layers", "final_norm", "hidden_norm", "layers",
        "token_norm"]
    assert sorted(shapes["mtp"]["layers"]) == attention
    assert sorted(shapes["mtp"]["expert_layers"]) == experts
    assert shapes["mamba2_layers"]["ssd_in"].shape == (
        5, 64, 2 * 128 + 2 * 64 + 8)
    assert shapes["expert_layers"]["moe_up"].shape == (5, 16, 32, 48)
    assert shapes["expert_layers"]["shared_up"].shape == (5, 64, 96)
    assert shapes["mtp"]["eh_proj"].shape == (128, 64)
    # every leaf has a partition rule of its own kind
    import re
    rules = llama_partition_rules()
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        name = "/".join(str(k.key) for k in path)
        spec = next(s for pat, s in rules if re.search(pat, name))
        assert len(spec) == leaf.ndim, (name, spec)


def _silu_for_relu2(monkeypatch):
    monkeypatch.setattr(ref, "_relu2_act", jax.nn.silu)


def _gate_after_the_norm(monkeypatch):
    def norm_then_gate(y, z, gain, groups, eps):
        y = y.reshape(*y.shape[:-1], groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return y.reshape(z.shape) * gain * jax.nn.silu(z)
    monkeypatch.setattr(ref, "_gated_group_norm", norm_then_gate)


def _target_shifted_by_one(monkeypatch):
    monkeypatch.setattr(ref, "_token_after", lambda targets: targets)


def _latent_under_the_shared_expert(monkeypatch):
    whole = ref.nemotronh_expert_layer

    def faulty(h, lp, cfg):
        _, routed = whole(h, lp, cfg)
        return ref._relu2(h @ lp["moe_lat_down"], lp["shared_up"],
                          lp["shared_down"]), routed
    monkeypatch.setattr(ref, "nemotronh_expert_layer", faulty)


@functools.lru_cache(maxsize=None)
def _the_program_meets_the_reference():
    """-> (cfg, params, batch, the program's loss and gradients), which
    the SOUND reference agrees with; once for the four faults."""
    cfg = _cfg((M, E, A), moe_latent=64)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = _loss_and_grads(lambda p: llama_loss(p, batch, cfg),
                                  params)
    sound = _loss_and_grads(lambda p: ref.nemotronh_loss(p, batch, cfg),
                            params)
    assert _worst_leaf(grads, sound[1])[0] < GRAD_TOL
    return cfg, params, batch, loss, grads


@pytest.mark.parametrize("plant", [
    _target_shifted_by_one, _latent_under_the_shared_expert,
    _silu_for_relu2, _gate_after_the_norm])
def test_a_planted_fault_is_refused(monkeypatch, plant):
    """The reference with one fault planted (the MTP target shifted by
    one and not two, the latent projection in front of the shared expert
    too, SiLU for ReLU-squared, the gate behind the group norm) no longer
    agrees with the program: the loss or a gradient leaf stands a
    thousand tolerances off. (A latent as wide as the model, so that the
    shared expert takes either input.)"""
    cfg, params, batch, loss, grads = _the_program_meets_the_reference()
    plant(monkeypatch)
    want, want_grads = _loss_and_grads(
        lambda p: ref.nemotronh_loss(p, batch, cfg), params)
    off = max(abs(float(loss) - float(want)) / float(want) / TOL,
              _worst_leaf(grads, want_grads)[0] / GRAD_TOL)
    assert off > 1e3, off


@pytest.mark.parametrize("field", [
    dict(one_part_layers=True, layer_types=(A, A)),
    dict(one_part_layers=True, layer_types=(M, M), **SSD),
    dict(ffn_act="relu2"),
    dict(n_experts=4, moe_latent=32),
    dict(n_experts=4, n_shared_experts=1, shared_d_ff=96),
    dict(one_part_layers=True, layer_types=(A, E), n_experts=4, **MTP)],
    ids=["one-part", "mamba2", "relu2", "latent", "shared-width", "mtp"])
def test_decode_serving_and_the_pipeline_refuse_the_new_fields(field):
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field) - {"n_experts"}
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    named = "|".join(sorted(set(field) & {
        "one_part_layers", "ffn_act", "moe_latent", "shared_d_ff",
        "mtp_layers"}))
    with pytest.raises(ValueError, match="training only") as said:
        gen.llama_prefill(params, prompt, cfg)
    assert all(f in str(said.value) for f in field if f != "n_experts")
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match=f"({named}).*no pipeline"):
        _validate_pipeline(cfg, 2, mesh, "seq", 2)


@pytest.mark.parametrize("seq_parallel", ["ring", "ulysses"])
@pytest.mark.parametrize("field, named", [
    (dict(layer_types=(A, A)), "one_part_layers"),
    (dict(layer_types=(A, E), **EXPERTS, **MTP), "mtp_layers")])
def test_the_sequence_axes_refuse_the_new_fields(seq_parallel, field,
                                                 named):
    cfg = LlamaConfig.tiny(dtype="float32", one_part_layers=True,
                           seq_parallel=seq_parallel,
                           **{"moe_impl": "auto", **field})
    params = llama_init(cfg, jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:2]).reshape(1, 1, 2, 1),
        ("data", "fsdp", "seq", "tensor"))
    with pytest.raises(ValueError, match=f"{named}.*sequence-parallel"):
        jax.eval_shape(lambda p: llama_loss(
            p, _batch(cfg, (2, 64)), cfg, mesh), params)


def test_the_mixer_refuses_a_split_mesh():
    cfg = _cfg((M, A), mtp=False, n_experts=0, **{
        k: v for k, v in dict(
            n_experts_per_token=2, moe_d_ff=0, n_shared_experts=0,
            shared_d_ff=0, moe_latent=0, score_func="softmax",
            route_scale=1.0, moe_aux_weight=0.01, moe_impl="auto").items()})
    params = _params(cfg)
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:2]).reshape(1, 1, 1, 2),
        ("data", "fsdp", "seq", "tensor"))
    with pytest.raises(ValueError, match="runs whole on each device"):
        jax.eval_shape(lambda p: llama_loss(
            p, _batch(cfg, (2, 64)), cfg, mesh), params)


@pytest.mark.parametrize("bad, match", [
    (dict(layer_types=(E, A)), "unknown layer type"),
    (dict(one_part_layers=True, layer_types=(M, A), conv_taps=4),
     "five sizes"),
    (dict(one_part_layers=True, layer_types=(M, A),
          **{**SSD, "ssd_groups": 3}), "groups"),
    (dict(one_part_layers=True, layer_types=(A, E)), "n_experts"),
    (dict(one_part_layers=True), "layer_types"),
    (dict(ffn_act="gelu"), "ffn_act"),
    (dict(moe_latent=32), "n_experts is 0"),
    (dict(n_experts=4, shared_d_ff=96), "n_shared_experts"),
    (dict(mtp_layers=1), "come together"),
    (dict(mtp_layers=2, mtp_types=(A,), mtp_weight=0.1), "ONE module"),
    (dict(n_experts=4, moe_latent=32, moe_impl="gshard"), "grouped")],
    ids=["experts-two-part", "no-sizes", "groups", "no-experts",
         "no-types", "act", "latent", "shared", "mtp-alone", "mtp-two",
         "gshard"])
def test_the_configuration_refuses(bad, match):
    with pytest.raises(ValueError, match=match):
        cfg = LlamaConfig.tiny(dtype="float32", **bad)
        # what only the layer can refuse: the GShard dispatch
        jax.eval_shape(lambda p: llama_loss(p, _batch(cfg), cfg),
                       jax.eval_shape(lambda k: llama_init(cfg, k),
                                      jax.random.PRNGKey(0)))


def test_a_configuration_without_the_new_fields_builds_what_it_built():
    """None of the new fields set: no new leaf, no new stack, and the
    fields do not count as set."""
    new = {"one_part_layers", "ssd_heads", "ssd_head_dim", "ssd_state",
           "ssd_groups", "ssd_chunk", "ffn_act", "moe_latent",
           "shared_d_ff", "mtp_layers", "mtp_types", "mtp_weight"}
    for cfg in (LlamaConfig.tiny(), LlamaConfig.tiny_moe(),
                LlamaConfig.tiny_moe(n_shared_experts=1,
                                     score_func="sigmoid")):
        assert not set(cfg.training_only_fields()) & new
        shapes = jax.eval_shape(lambda k, cfg=cfg: llama_init(cfg, k),
                                jax.random.PRNGKey(0))
        assert not {"mamba2_layers", "expert_layers", "mtp"} & set(shapes)
        assert not [k for stack in shapes.values()
                    if isinstance(stack, dict) for k in stack
                    if k.startswith(("ssd_", "moe_lat_"))]
    moe = jax.eval_shape(lambda k: llama_init(LlamaConfig.tiny_moe(
        n_shared_experts=1), k), jax.random.PRNGKey(0))
    assert sorted(moe["layers"]) == [
        "attn_norm", "mlp_norm", "moe_down", "moe_gate", "moe_up",
        "router", "shared_down", "shared_gate", "shared_up", "wk", "wo",
        "wq", "wv"]
    # and an older configuration's weights did not move: the keys are
    # dealt in the order they always were
    cfg = LlamaConfig.tiny_moe(n_shared_experts=1)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    k = jax.random.split(jax.random.PRNGKey(0), 16)
    wq = jax.random.normal(k[0], (2, 64, 64), F32) * 64 ** -0.5
    assert float(jnp.max(jnp.abs(params["layers"]["wq"] - wq))) == 0.0


def test_a_dense_relu2_ffn_has_two_matrices():
    cfg = LlamaConfig.tiny(dtype="float32", ffn_act="relu2")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert "w_gate" not in params["layers"]
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    with jax.default_matmul_precision("highest"):
        y, _ = _ffn(h, lp, cfg)
        want = ref._relu2(h, lp["w_up"], lp["w_down"])
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
