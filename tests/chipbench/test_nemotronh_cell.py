"""The NVIDIA-Nemotron-3-Super-120B-A12B configuration, its counts, its
readers and its adapter on the CPU: published widths against the catalog,
``reduced``, the counts against hand counts, the six readers on a
hand-built trace (``None`` where the program has no such scope),
``child.measure`` through the adapter's whole ``check_outputs`` at a tiny
size, the fp8 control, and the benchmark's reference against the
program's. Entries are found by NAME, never by position: the next cell
can be appended."""

import dataclasses
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "nemotron3super.spmd.b1s8192"
NAME = "nemotron-3-super-120b-a12b"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
# The catalog's `config` for NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (the
# model-configs guide's architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
NEW_METRICS = ("ssd_core_ms_per_step", "ssd_core_roofline_pct",
               "ssd_chain_ms_per_step", "ssd_proj_ms_per_step",
               "moe_latent_ms_per_step", "mtp_ms_per_step")
SCOPE_OF = {"ssd_core_ms_per_step": "hvd.ssd.core",
            "ssd_chain_ms_per_step": "hvd.ssd.chain",
            "ssd_proj_ms_per_step": "hvd.ssd.proj",
            "moe_latent_ms_per_step": "hvd.moe.latent",
            "mtp_ms_per_step": "hvd.mtp"}
M, A, E = "mamba2", "full_attention", "experts"


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert list(cfg["reduced"]) == REDUCED
    cut = cfg["reduced"]
    assert (cut["num_hidden_layers"]["published"],
            cut["num_hidden_layers"]["here"], cfg["num_hidden_layers"]) \
        == (88, 11, 11)
    assert cut["hybrid_override_pattern"]["published"] == PATTERN \
        and len(PATTERN) == 88
    # the floors: one whole period in the published 5 : 5 : 1, 8 routed
    # experts a layer, an eighth of the vocabulary, every width published
    assert cfg["hybrid_override_pattern"] == PATTERN[27:38] \
        == "MEMEMEMEM*E" == cut["hybrid_override_pattern"]["here"]
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) \
        == (40, 40, 8) and "27-37" in cut["num_hidden_layers"]["kept"]
    assert (cut["n_routed_experts"]["published"],
            cut["n_routed_experts"]["here"], cfg["n_routed_experts"]) \
        == (512, 8, 8) and "64 chips" in cut["n_routed_experts"]["held"]
    assert (cut["vocab_size"]["published"], cut["vocab_size"]["here"],
            cfg["vocab_size"]) == (131072, 16384, 131072 // 8)
    for key in cut.values():
        assert key.get("kept") or key.get("held")
    a = cfg["assumed"]
    assert a["param_dtype"] == "bfloat16" and a["remat"] \
        and a["loss_chunk"] > 0 and a["optimizer"]["name"] == "adam" \
        and a["optimizer"]["learning_rate"] == 1e-5 \
        and a["mtp_weight"] == 0.1 and a["ssd_chunk"] == cfg["chunk_size"]
    for said in ("why", "mtp_weight_why", "parameters", "remat_why",
                 "loss_chunk_why", "ssd_chunk_why", "init"):
        assert a[said] and "PLACEHOLDER" not in a[said], said
    assert "1,378.7 M" in a["parameters"] and "1,378.7 M" \
        in cfg["stands_for"] and "SIXTY-FOUR" in cfg["stands_for"] \
        and cfg["why"]
    assert a["compiler_options"]["xla_tpu_scoped_vmem_limit_kib"]


def test_the_entries_fields():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == REDUCED
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
        "spmd_dispatch_ms_per_step.lm", "flash_bwd_ms_per_step",
        "setup_compile_s", *NEW_METRICS}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == ("kernels" if "core" in name else "model")
        assert m["unit"] == ("%" if "pct" in name else "ms")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))
    # a quarter of the cells, rounded down, may take four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(len(bench["workloads"]) // 4, 1)


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import child, ssd_counts
    from horovod_tpu.models import llama_init

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"], traffic["warmup_steps"],
            traffic["calibration_steps"], traffic["traced_steps"]) \
        == (1, 8192, 1, "spmd", 2, 3, 5)
    mod = child.load_file("models", "nemotronh")
    model = mod.Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size,
            c.n_layers, c.conv_taps, c.norm_eps) == (
        4096, 32, 2, 128, 16384, 11, 4, 1e-5)
    assert (c.ssd_heads, c.ssd_head_dim, c.ssd_state, c.ssd_groups,
            c.ssd_chunk, c.ssd_d_inner, c.mamba_conv_bias) == (
        128, 64, 128, 8, 128, 8192, True)
    assert (c.n_experts, c.n_experts_held, c.first_expert,
            c.n_experts_per_token, c.expert_width, c.moe_latent,
            c.shared_width, c.route_scale, c.score_func, c.ffn_act) == (
        512, 8, 0, 22, 2688, 1024, 5376, 5, "sigmoid", "relu2")
    assert c.one_part_layers and c.norm_topk_prob and c.loss_chunk \
        and not (c.tie_embeddings or c.qk_norm or c.attn_gate
                 or c.rope_full_attention or c.moe_aux_weight)
    assert (c.mtp_layers, c.mtp_types, c.mtp_weight) == (1, (A, E), 0.1)
    assert c.layer_types == (M, E, M, E, M, E, M, E, M, A, E)
    assert not any(s.rope for s in c.layer_plan() + c.layer_plan(True))
    assert model.units_per_step == 8192 and model.even_share == 2816 \
        and model.row_bound() == 5632
    # ISSUE 50's arithmetic: a Mamba-2 layer 109.64 M, an attention layer
    # 35.66 M, an expert layer outside its experts 54.53 M, a routed
    # expert 5.505 M, the MTP module 167.8 M: 1,378.7 M.
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "expert_layers", "final_norm",
                              "layers", "lm_head", "mamba2_layers", "mtp"]
    n = sum(x.size for x in jax.tree.leaves(shapes))
    mamba2 = 4096 * 18560 + 10240 * 5 + 3 * 128 + 8192 + 8192 * 4096 + 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    outside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    mtp = 8192 * 4096 + 3 * 4096 + attn + outside + 8 * expert
    assert [round(x / 1e6, 2) for x in (mamba2, attn, outside)] \
        == [109.64, 35.66, 54.53] and round(expert / 1e6, 3) == 5.505
    assert round(mtp / 1e6, 1) == 167.8
    assert n == 5 * mamba2 + attn + 5 * (outside + 8 * expert) \
        + 2 * 16384 * 4096 + 4096 + mtp
    assert round(n / 1e6, 1) == 1378.7
    assert shapes["mamba2_layers"]["ssd_in"].shape == (5, 4096, 18560)
    assert shapes["expert_layers"]["moe_up"].shape == (5, 8, 1024, 2688)
    assert shapes["expert_layers"]["router"].shape == (5, 4096, 512)
    assert shapes["mtp"]["expert_layers"]["moe_down"].shape == (
        1, 8, 2688, 1024)
    assert shapes["layers"]["wk"].shape == (1, 4096, 256)
    # rescale_prenorm_residual by the PUBLISHED depth
    assert model.out_scale == 88 ** -0.5
    # the counts: the matmul parameters a token passes
    kinds, mtp_kinds = [M, None] * 4 + [M, "attention", None], \
        ["attention", None]
    assert model._kinds() == kinds and model._kinds(True) == mtp_kinds
    share = 2816 / 8192
    p = mod.matmul_params_per_token(c, kinds, mtp_kinds, share)
    part_m = 4096 * 18560 + 8192 * 4096
    part_a = 4096 * 128 * 68
    part_e = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + share * 2 * 1024 * 2688
    assert p == pytest.approx(5 * part_m + 2 * part_a + 6 * part_e
                              + 2 * 4096 * 4096 + 2 * 4096 * 16384)
    flops, nbytes = model.ssd_work()
    assert flops == 5 * 3 * 4 * 128 * 64 * 128 * 8192
    assert model.flops_per_unit() == pytest.approx(
        6 * p + 2 * 12 * 32 * 128 * (8192 * 8193 // 2) / 8192
        + flops / 8192)
    # x, y in bf16, dt in float32 a head, B and C in bf16 a group:
    # forward 37,376 B a token, backward 58,368
    assert ssd_counts.core_bytes(1, 128, 64, 128, 8, 1) == 37376 + 58368
    assert nbytes == 5 * 8192 * (37376 + 58368)
    # bytes bind: 0.96 ms a layer against 0.52 ms of FLOPs
    floor = ssd_counts.floor_s("TPU v5 lite", flops, nbytes)
    assert floor == nbytes / 819e9 and round(floor * 1e3 / 5, 2) == 0.96
    assert round(flops / 197e12 * 1e3 / 5, 2) == 0.52


def _ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.ssd.proj`` (400 ns), the ``while`` and the gather in its body
    under ``hvd.ssd.core`` (400 + 400), the recomputed elementwise
    fusion under ``hvd.ssd.chain`` (100), over two steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in rename if isinstance(rename, tuple) else (
                ("hvd.attn.proj", "hvd.ssd.proj"),
                ("hvd.moe.dispatch", "hvd.ssd.core"),
                ("hvd.ffn", "hvd.ssd.chain")):
            text = text.replace(old, new)
    ctx = t._ctx(monkeypatch, [("jit_hvd_grad", text),
                               ("jit_hvd_apply", t.APPLY)])
    ctx.model = model if model is not None else types.SimpleNamespace()
    return ctx


def test_the_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child

    # 96 ns of required work at the HBM peak, 2 FLOPs
    model = types.SimpleNamespace(ssd_work=lambda: (2, 96e-9 * 819e9))
    ctx = _ctx(monkeypatch, model)
    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    assert read["ssd_proj_ms_per_step"](ctx) == pytest.approx(400 / 1e6 / 2)
    assert read["ssd_core_ms_per_step"](ctx) == pytest.approx(800 / 1e6 / 2)
    assert read["ssd_chain_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    assert read["moe_latent_ms_per_step"](ctx) is None
    assert read["mtp_ms_per_step"](ctx) is None
    ctx = _ctx(monkeypatch, model, rename=(
        ("hvd.attn.proj", "hvd.moe.latent"), ("hvd.ffn", "hvd.mtp")))
    assert read["moe_latent_ms_per_step"](ctx) == pytest.approx(
        400 / 1e6 / 2)
    assert read["mtp_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["ssd_core_roofline_pct"](_ctx(monkeypatch, model)) \
        == pytest.approx(100.0 * 96 / 400)
    # a model kind that counts no such work: nothing, and no exception
    assert read["ssd_core_roofline_pct"](_ctx(monkeypatch)) is None


def test_each_new_scope_is_named_by_one_reader():
    """``tests/chipbench/test_scope_metrics.py`` holds the table minus
    what some reader names to a fixed set: each of the five new scopes is
    named by exactly one of the new readers' calls."""
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

    model = types.SimpleNamespace(ssd_work=lambda: (2, 96.0))
    read = child.load_reader(metric).read
    assert read(_ctx(monkeypatch, model, rename=False)) is None
    ctx = _ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None

    def before_the_scopes(ctx, *names, **_):
        raise ValueError(f"no device scopes: {names}")

    monkeypatch.setattr(scopes, "ms_per_step", before_the_scopes)
    assert read(_ctx(monkeypatch, model)) is None


TINY = {
    "kind": "nemotronh", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mtp_hybrid_override_pattern": "*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 48,
    "layer_norm_epsilon": 1e-5, "conv_kernel": 4, "use_conv_bias": True,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 32,
    "n_groups": 2, "chunk_size": 32, "expand": 2, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 5, "num_nextn_predict_layers": 1,
    "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
    "tie_word_embeddings": False, "use_bias": False,
    "mamba_proj_bias": False, "mlp_bias": False, "attention_bias": False,
    "rescale_prenorm_residual": True,
    "reduced": {"n_routed_experts": {"published": 16, "here": 4},
                "num_hidden_layers": {"published": 10, "here": 5}},
    "assumed": {"remat": "attn", "param_dtype": "float32",
                "loss_chunk": 64, "mtp_weight": 0.1,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 1, "seq": 160,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
MAIN = {"embed", "final_norm", "lm_head", "ssd_norm", "ssd_in", "ssd_conv",
        "ssd_conv_bias", "ssd_dt_bias", "ssd_a_log", "ssd_d",
        "ssd_out_norm", "ssd_out", "attn_norm", "wq", "wk", "wv", "wo",
        "mlp_norm", "router", "expert_bias", "moe_up", "moe_down",
        "moe_lat_down", "moe_lat_up", "shared_up", "shared_down"}
MODULE = {"mtp." + x for x in (
    "token_norm", "hidden_norm", "eh_proj", "final_norm", "attn_norm",
    "wq", "wk", "wv", "wo", "mlp_norm", "router", "expert_bias", "moe_up",
    "moe_down", "moe_lat_down", "moe_lat_up", "shared_up", "shared_down")}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "nemotronh")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    assert model.cfg.layer_types == (M, E, M, A, E)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (flash,) = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert flash["shape"] == [1, 160, 4, 16] and flash["kv_heads"] == 2
    assert max(flash["err"].values()) < 6e-3
    (ssd,) = [s for s in said if s["event"] == "ssd_vs_token_by_token"]
    assert ssd["shape"] == [1, 160, 8, 16] and ssd["states"] == 32 \
        and ssd["groups"] == 2
    assert set(ssd["err"]) == {"fwd", "dX", "ddt", "dA", "dB", "dC", "dD"}
    # bf16 operands: scores, dt x and the state rounded as they enter a
    # matmul
    assert max(ssd["err"].values()) < 1e-2
    assert ssd["required_flops_per_step"] == 2 * 3 * 4 * 8 * 16 * 32 * 160
    gmm = [s for s in said if s["event"] == "grouped_mm_vs_numpy"]
    assert [(s["which"], s["shape"]) for s in gmm] == [
        ("up", [[240, 32], [4, 32, 48]]), ("down", [[240, 48], [4, 48, 32]])]
    assert max(max(s["err"].values()) for s in gmm) < 6e-3
    (load,) = [s for s in said if s["event"] == "expert_load"]
    assert len(load["rows_held_per_layer"]) == 2 \
        and load["rows_an_even_router_hands_this_chip"] == 120
    # the step: every leaf of the tree, the MTP module's too, in both
    # readings; both loss terms
    (step,) = [s for s in said if s["event"] == "step_vs_reference"]
    assert (step["tokens"], step["on"]) == (160, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss", "mtp_loss"} \
        | {k + x for x in MAIN | MODULE for k in ("d_", "moved_")}
    assert step["reference_mtp_term"] > 0.1
    assert step["loss"] == pytest.approx(
        step["reference_main_term"] + 0.1 * step["reference_mtp_term"],
        rel=1e-4)
    # The lowering: a fault is reported, not swallowed.
    kernels = " tpu_custom_call hvd_flash_fwd hvd_ssd_fwd hvd_ssd_bwd " \
        "@gmm @tgmm "
    assert model.check_lowering("tensor<1x160x128xf32>", False) is None
    assert model.check_lowering(kernels, True) is None
    assert "materialised" in model.check_lowering(
        "tensor<160x8x16x32xf32>", False)
    for name in kernels.split():
        assert name in model.check_lowering(kernels.replace(name, ""), True)


def test_a_dropped_mtp_term_is_refused():
    """The program trained without its second loss term (``mtp_weight``
    next to nothing): the loss alone would pass (a tenth of a term in
    the sum), ``mtp_loss`` and the module's gradients do not."""
    child, mod, lane, model = _tiny()
    dropped = dataclasses.replace(model.cfg, mtp_weight=1e-9)
    loss = model.loss
    model.loss = lambda params, state, batch: type(model).loss(
        types.SimpleNamespace(cfg=dropped), params, state, batch)
    r = child.measure(model, lane, TINY_TRAFFIC, seed=5, seconds=0.2,
                      trace=False, t0=time.time(), say=lambda **k: None)
    model.loss = loss
    faults = [f for f in r["faults"] if f.startswith("the step")]
    assert any("mtp_loss" in f for f in faults)
    assert any("d_mtp.eh_proj" in f for f in faults)


def test_fp8_in_the_programs_place_is_refused_by_every_comparison():
    child, mod, lane, model = _tiny(control=True)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=11, seconds=0.2,
                      trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert any(s["event"] == "the_reference_in_fp8_in_the_programs_place"
               for s in said)
    for kind in mod.COMPARISONS:
        assert [f for f in r["faults"] if f.startswith(kind)], kind
    assert len([f for f in r["faults"] if f.startswith("ssd")]) >= 5


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, one part at a
    time, the gradients chained by hand through the MTP module) and the
    program's (horovod_tpu/models/reference.py) agree on the loss, its
    two terms and every gradient leaf to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import nemotronh_loss

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    seen = {}
    loss, main, mtp, loads = mod.reference_loss_and_grads(
        params, batch, c, lambda where, g: seen.setdefault(
            where, {}).update(g))
    with jax.default_matmul_precision("highest"):
        want, grads = jax.jit(jax.value_and_grad(
            lambda p: nemotronh_loss(p, batch, c)))(params)
        terms = jax.jit(lambda p: nemotronh_loss(p, batch, c, terms=True))(
            params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert float(main) == pytest.approx(float(terms[0]), rel=1e-5)
    assert float(mtp) == pytest.approx(float(terms[1]), rel=1e-5)
    assert len(loads) == 2 and all(x.shape == (4,) for x in loads)
    compared = set()
    for where, got in seen.items():
        tree = grads
        for name in where[:-2] if len(where) > 1 else where:
            tree = tree[name]
        for name, g in got.items():
            w = tree[where[-2]][name][where[-1]] if len(where) > 1 \
                else tree[name]
            err = float(jnp.linalg.norm(g - w)
                        / (jnp.linalg.norm(w) + 1e-30))
            assert err < 5e-5, (where, name, err)
            compared.add((where[:-1] if len(where) > 1 else where, name))
    assert len(compared) == len(jax.tree.leaves(grads))
