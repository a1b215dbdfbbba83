"""The Xing4.0-29B-A4B configuration, its counts, its readers and its
adapter on the CPU: published widths against the catalog row key by key,
``reduced`` and ``assumed`` complete, the floors, the entries found BY
NAME (never by position: the next cell can be appended), the parameter
count of ISSUE 57's table from ``llama_init``'s leaves, the four readers
on a hand-built trace (``None`` where the program has no such scope),
``mla_counts`` against numbers worked by hand, the adapter's whole
``check_outputs`` at a tiny size, the fp8 control, and the benchmark's
reference against the program's."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "xing4.spmd.b1s8192"
NAME = "xing4.0-29b-a4b"
REDUCED = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1),
           "n_routed_experts": (64, 8), "vocab_size": (131072, 16384)}
# The catalog's `config` for Xing4.0-29B-A4B (the model-configs guide's
# architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128}
NEW_METRICS = ("mla_core_ms_per_step", "mla_core_roofline_pct",
               "mla_proj_ms_per_step", "hc_mix_ms_per_step")
SCOPE_OF = {"mla_core_ms_per_step": "hvd.mla.core",
            "mla_proj_ms_per_step": "hvd.mla.proj",
            "hc_mix_ms_per_step": "hvd.hc.mix"}
# ISSUE 57's table at FOUR expert layers (the floor it allows where the
# chip's reading says five do not fit beside two sets of gradients):
# dense + 4 expert layers + MTP + vocabulary.
PARAMETERS = 1_041_900_026 - 128_426_358


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _model(config=None, traffic=None):
    from chipbench import child

    return child.load_file("models", "xing4").Model(
        config or _config(), traffic or {"batch": 1, "seq": 8192})


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert cfg["kind"] == "xing4" and cfg["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert set(cfg["reduced"]) == set(REDUCED)
    for key, (published, here) in REDUCED.items():
        r = cfg["reduced"][key]
        assert (r["published"], r["here"]) == (published, here) \
            and cfg[key] == here and r["kept"], key
    # nothing else differs from the catalog's row, and no width is cut
    assert set(cfg) - set(PUBLISHED) - set(REDUCED) == {
        "kind", "source", "stands_for", "why", "reduced", "assumed"}
    # the floors: an eighth of the experts and of the vocabulary, one
    # leading dense layer, at least four expert layers
    a = cfg["assumed"]
    assert cfg["n_routed_experts"] * a["shares_a_layer"] == 64
    assert cfg["vocab_size"] * a["shares_a_layer"] == 131072
    assert cfg["first_k_dense_replace"] == 1
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    for said in ("param_dtype", "optimizer", "remat", "loss_chunk",
                 "compiler_options", "layers", "latent_norms",
                 "rope_pairing", "yarn", "router", "hyper_connections",
                 "mtp", "mtp_weight"):
        assert a[said], said
    assert a["optimizer"] == {
        "name": "adam", "learning_rate": 1e-05,
        "learning_rate_why": a["optimizer"]["learning_rate_why"]}
    assert "m^2 / sqrt(192)" in a["yarn"] and "1.4159" in a["yarn"]
    assert "not the identity" in a["hyper_connections"]
    assert "913.5 M" in cfg["stands_for"] \
        and "eight-way expert parallelism" in cfg["stands_for"] \
        and "no one real pipeline stage" in cfg["why"]


def test_the_entries_fields():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b1s8192", NAME)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "tokens_per_s", "step_ms_p90", "peak_hbm_gb", "setup_s",
        "device_idle_pct.lm", "optimizer_ms_per_step.lm",
        "spmd_dispatch_ms_per_step.lm", "setup_compile_s",
        "flash_bwd_ms_per_step", "moe_gmm_ms_per_step",
        "moe_gmm_roofline_pct", "moe_dispatch_ms_per_step", *NEW_METRICS}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == ("kernels" if "core" in name else "model")
        assert m["unit"] == ("%" if "pct" in name else "ms")
        assert m["better"] == ("higher" if "pct" in name else "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))
    # a quarter of the cells, rounded down, may take four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(len(bench["workloads"]) // 4, 1)


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from horovod_tpu.models import LlamaConfig

    model = _model()
    c = model.cfg
    assert isinstance(c, LlamaConfig)
    assert (c.d_model, c.n_heads, c.d_ff, c.expert_width, c.shared_width) \
        == (3584, 32, 9216, 1024, 1024)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.qk_head_dim) \
        == (768, 512, 128, 64, 128, 192)
    assert c.rope_yarn == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_clamp) \
        == (4, 20, 1e-6, (-30.0, 30.0))
    assert (c.n_experts, c.n_experts_held, c.first_expert,
            c.n_experts_per_token, c.score_func, c.route_scale,
            c.norm_topk_prob, c.n_shared_experts, c.moe_aux_weight) \
        == (64, 8, 0, 4, "sigmoid", 2, True, 1, 0.0)
    assert (c.n_layers, c.n_dense_layers, c.mtp_layers, c.mtp_types,
            c.mtp_weight, c.vocab_size) \
        == (5, 1, 1, ("full_attention",), 0.1, 16384)
    assert [(s.stack, s.index, bool(s.dense_ffn)) for s in c.layer_plan()] \
        == [("dense_layers", 0, True)] + [("layers", i, False)
                                          for i in range(4)]
    # the scale and the frequencies of the published YaRN keys
    inv, mult, scale = c.yarn()
    assert mult == 1.0 and scale == pytest.approx(
        1.4158883083359673 ** 2 / 192 ** 0.5, rel=1e-12)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        10000 ** (-62 / 64) / 64, rel=1e-6)
    # ISSUE 57's table, from llama_init's leaves (shapes only)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(                               # noqa: E731
        x.size for x in jax.tree.leaves(tree))
    assert count(shapes) == PARAMETERS
    layer = lambda stack: count(stack) // jax.tree.leaves(  # noqa: E731
        stack)[0].shape[0]
    attention = 3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * 3584
    hc = 2 * (14336 * 24 + 24 + 3)
    expert = 3 * 3584 * 1024
    assert layer(shapes["dense_layers"]) \
        == attention + 3 * 3584 * 9216 + hc + 2 * 3584
    assert layer(shapes["layers"]) == attention + hc + 2 * 3584 \
        + 3584 * 64 + 64 + 9 * expert == 128_426_358
    assert count(shapes["mtp"]) == layer(shapes["layers"]) \
        + 7168 * 3584 + 3 * 3584
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 16384 * 3584
    # what multiplies a token, and the step's required work
    assert model.matmul_params_per_token() == pytest.approx(500.5e6,
                                                            rel=1e-3)
    assert model.units_per_step == 8192
    flops, nbytes = model.mla_work()
    assert flops == 3 * 2 * 320 * 32 * (8192 * 8193 // 2) * 6
    assert nbytes == 6 * 320 * 32 * 2 * 8192 * 6
    assert model.hc_floor_bytes() == 25 * 3584 * 2 * 8192 * 12
    assert len(model._rows()) == 5 and model.even_share == 4096


def test_the_counts_by_hand_at_a_small_shape():
    """T = 5: 15 causal pairs a head. Queries and keys 6 wide beside
    values 4 wide: 2 x 10 FLOPs a pair and head forward, three times
    that a step."""
    from chipbench import mla_counts

    assert mla_counts.core_pairs(5) == 15
    assert mla_counts.core_flops(2, 5, 3, 6, 4, 7) \
        == 3 * 20 * 3 * 2 * 15 * 7
    # a head and token: q, k 6 + 6 and v, o 4 + 4 values forward; q, k,
    # v, o, do 24 and dq, dk, dv 16 backward: 60 values of 2 bytes
    assert mla_counts.core_bytes(2, 5, 3, 6, 4, 7) \
        == 60 * 2 * 3 * 2 * 5 * 7
    # four streams of 8: (5 x 4 + 5) x 8 values a token and part
    assert mla_counts.hc_bytes(5, 4, 8, 3) == 25 * 8 * 2 * 5 * 3
    assert mla_counts.floor_s("TPU v5 lite", 197e12, 1.0) == 1.0
    assert mla_counts.floor_s("TPU v5 lite", 1.0, 819e9) == 1.0


def _ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.mla.proj`` (400 ns), the ``while`` and the gather in its body
    under ``hvd.mla.core`` (400 + 400), the recomputed elementwise
    fusion under ``hvd.hc.mix`` (100), over two steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in (("hvd.attn.proj", "hvd.mla.proj"),
                         ("hvd.moe.dispatch", "hvd.mla.core"),
                         ("hvd.ffn", "hvd.hc.mix")):
            text = text.replace(old, new)
    ctx = t._ctx(monkeypatch, [("jit_hvd_grad", text),
                               ("jit_hvd_apply", t.APPLY)])
    ctx.model = model if model is not None else types.SimpleNamespace()
    return ctx


def test_the_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child

    # 96 ns of required work at the HBM peak, 2 FLOPs
    work = lambda: (2, 96e-9 * 819e9)                        # noqa: E731
    model = types.SimpleNamespace(mla_work=work)
    ctx = _ctx(monkeypatch, model)
    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    assert read["mla_proj_ms_per_step"](ctx) == pytest.approx(400 / 1e6 / 2)
    assert read["mla_core_ms_per_step"](ctx) == pytest.approx(800 / 1e6 / 2)
    assert read["hc_mix_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["mla_core_roofline_pct"](_ctx(monkeypatch, model)) \
        == pytest.approx(100.0 * 96 / 400)
    # a model kind that counts no such work: nothing, and no exception
    assert read["mla_core_roofline_pct"](_ctx(monkeypatch)) is None


def test_each_new_scope_is_named_by_one_reader():
    import re

    from horovod_tpu.utils.spans import SCOPES

    named = []
    for metric in NEW_METRICS:
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               metric + ".py")) as f:
            named += re.findall(r"ms_per_step\(ctx, \"([a-z.0-9]+)\"",
                                f.read())
    assert sorted("hvd." + s for s in named) == sorted(SCOPE_OF.values())
    assert set(SCOPE_OF.values()) <= SCOPES


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_scope_reads_nothing(monkeypatch, metric):
    """A model with no such layer (the scopes of another), a program
    with no scope tables at all, and a program from before the scopes
    (its table does not know the name, as the parent commit's does
    not): None, never 0, no exception."""
    from chipbench import child, scopes

    model = types.SimpleNamespace(mla_work=lambda: (2, 96.0))
    read = child.load_reader(metric).read
    assert read(_ctx(monkeypatch, model, rename=False)) is None
    ctx = _ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None

    def before_the_scopes(ctx, *names, **_):
        raise ValueError(f"no device scopes: {names}")

    monkeypatch.setattr(scopes, "ms_per_step", before_the_scopes)
    assert read(_ctx(monkeypatch, model)) is None


# ---------------------------------------------------------------------
# The adapter's comparisons at a tiny size (the published RATIOS: queries
# and keys one and a half times the values' width, four streams, twenty
# iterations, four of 64 experts a token with eight held).

TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 1, "seq": 64,
                "warmup_steps": 1, "calibration_steps": 1,
                "traced_steps": 1}


def _tiny(control=False, **assumed):
    from chipbench import child

    cfg = dict(_config(), hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               vocab_size=128, num_hidden_layers=3)
    cfg["assumed"] = dict(cfg["assumed"], loss_chunk=16, **assumed)
    mod = child.load_file("models", "xing4")
    return mod, (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        cfg, TINY_TRAFFIC)


def _checked(model):
    import jax

    said = []
    params, _ = jax.jit(model.init)(jax.random.PRNGKey(0))
    faults = model.check_outputs(params, jax.random.PRNGKey(1),
                                 lambda **f: said.append(f))
    return faults, {f["event"]: f for f in said}


# At 64 wide the logits' limit stands elsewhere than at 3584 (the chip's
# readings: 0.17 for the program, 0.54 for fp8): here the program reads
# 0.065 and the reference from fp8 0.25.
TINY_LOGITS_TOL = 0.12


def test_check_outputs_with_a_tiny_adapter_makes_every_comparison(
        monkeypatch):
    mod, model = _tiny(param_dtype="float32")
    monkeypatch.setattr(mod, "LOGITS_TOL", TINY_LOGITS_TOL)
    faults, said = _checked(model)
    flash, step = said["flash_vs_explicit_mask"], said["model_vs_reference"]
    assert flash["shape"] == [1, 64, 4, 24] and flash["value_width"] == 16
    assert set(flash["err"]) == {"fwd", "dq", "dk", "dv"}
    assert max(flash["err"].values()) < mod.KERNEL_TOL["fwd"]
    assert set(step["err"]) == {"logits", "mtp_logits"}
    assert set(step["routes"]) == {"layer 1", "layer 2", "mtp layer 0"}
    assert all(r["same_share"] >= mod.ROUTE_SAME_MIN
               and r["worst_margin"] <= mod.ROUTE_MARGIN_TOL
               for r in step["routes"].values())
    assert set(step["h_res_sums_off_one"]) == {"attn", "mlp"}
    assert all(off[stat] <= tol
               for off in step["h_res_sums_off_one"].values()
               for stat, tol in mod.HC_SUM_TOL.items())
    assert max(step["err"].values()) < TINY_LOGITS_TOL
    assert faults == [] and "planted" not in step


def test_fp8_in_the_programs_place_is_refused_by_every_comparison(
        monkeypatch):
    mod, control = _tiny(control=True, param_dtype="float32")
    monkeypatch.setattr(mod, "LOGITS_TOL", TINY_LOGITS_TOL)
    faults, said = _checked(control)
    for kind in mod.COMPARISONS:
        assert any(f.startswith(kind) for f in faults), (kind, faults)
    assert min(said["model_vs_reference"]["err"].values()) \
        > TINY_LOGITS_TOL
    # the faults the control plants in the PROGRAM, on the same reference
    planted = said["model_vs_reference"]["planted"]
    assert set(planted) == {"19 Sinkhorn iterations", *mod.MUST_REFUSE}
    for fault in mod.MUST_REFUSE:
        assert sum(f.startswith(f"planted {fault}: ") for f in faults) \
            == len(mod.COMPARISONS[1:]), (fault, planted)
    # (one iteration of twenty reads what the program itself reads)
    assert max(planted["19 Sinkhorn iterations"].values()) \
        < TINY_LOGITS_TOL


def test_a_router_that_swaps_experts_is_refused(monkeypatch):
    """The program's choice with its best expert replaced by its ninth:
    no near-tie, a margin."""
    import jax.numpy as jnp

    mod, model = _tiny(param_dtype="float32")
    monkeypatch.setattr(mod, "LOGITS_TOL", TINY_LOGITS_TOL)
    monkeypatch.setattr(
        mod, "_program_router", lambda c: lambda h, lp: jnp.argsort(
            -(h @ lp["router"]), -1)[..., jnp.array([8, 1, 2, 3])])
    faults, _ = _checked(model)
    assert any("swapped across a margin" in f for f in faults)
    assert any("chose the reference's experts" in f for f in faults)


def test_the_benchmarks_reference_is_the_programs():
    """The benchmark's copy (a layer at a time, attention in passes of
    query rows) against ``horovod_tpu/models/reference.py``'s on the same
    weights: the same logits, the model's and the MTP module's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import reference

    mod, model = _tiny(param_dtype="float32")
    params, _ = jax.jit(model.init)(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 128)
    targets = jnp.roll(tokens, -1, 1)
    got = mod.reference_logits(params, tokens, targets, model.cfg)
    want = jax.jit(lambda p: reference.xing4_forward(
        p, tokens, model.cfg, targets))(params)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    inv, mult, scale = mod.yarn(model.cfg)
    want = reference.xing4_yarn(model.cfg)
    np.testing.assert_allclose(inv, want[0], rtol=1e-7)
    assert (mult, scale) == pytest.approx(want[1:])
    np.testing.assert_allclose(inv, model.cfg.yarn()[0], rtol=1e-6)
