"""The Qwen3-Next-80B-A3B configuration, its counts, its readers and its
adapter on the CPU: published widths against the catalog, ``reduced``,
the counts against hand counts, the four ``gdn_*`` readers on a
hand-built trace (``None`` where the program has no such scope),
``child.measure`` through the adapter's whole ``check_outputs`` at a
tiny size, the fp8 control, and the benchmark's reference against the
program's."""

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

CELL = "qwen3next.spmd.b2s8192"
L, A = "linear_attention", "full_attention"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size", "layer_types"]
# The catalog's `config` for Qwen3-Next-80B-A3B-Instruct (the
# model-configs guide's architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 10, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}
NEW_METRICS = ("gdn_core_ms_per_step", "gdn_core_roofline_pct",
               "gdn_chain_ms_per_step", "gdn_proj_ms_per_step")


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    here = {k: cfg["reduced"][k]["here"] for k in REDUCED}
    assert here == {k: cfg[k] for k in REDUCED} == {
        "num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992,
        "layer_types": [L, L, L, A]}
    published = {k: cfg["reduced"][k]["published"] for k in REDUCED[:3]}
    assert published == {"num_hidden_layers": 48, "num_experts": 512,
                         "vocab_size": 151936}
    # the floors: one whole period of full_attention_interval, 8 routed
    # experts at least, an eighth of the vocabulary
    assert len(cfg["layer_types"]) % cfg["full_attention_interval"] == 0
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 == 151936
    a = cfg["assumed"]
    assert a["shares_a_layer"] == 16 and "SIXTEEN" in cfg["stands_for"]
    assert a["shares_a_layer"] * cfg["num_experts"] == 512
    assert a["first_expert"] == 0 and a["remat"] and cfg["why"]
    assert a["gdn_chunk"] == 64
    for said in ("router_aux_loss", "mtp_head", "zero_centred_norms",
                 "column_layout", "remat_why", "gdn_init"):
        assert a[said]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"]
             if c["name"] == "qwen3-next-80b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b2s8192", "qwen3-next-80b-a3b")
    assert len(cell["why"]) <= 200
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "tokens_per_s", "step_ms_p90", "peak_hbm_gb", "setup_s",
        "device_idle_pct.lm", "optimizer_ms_per_step.lm",
        "spmd_dispatch_ms_per_step.lm", "flash_bwd_ms_per_step",
        "setup_compile_s", "moe_gmm_ms_per_step", "moe_gmm_roofline_pct",
        "moe_dispatch_ms_per_step", *NEW_METRICS}
    # appended: what the benchmark had comes first, in its old order
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == list(NEW_METRICS)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == ("kernels" if "core" in m["name"] else "model")
    assert [w["name"] for w in bench["workloads"]][-1] == CELL


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import child, gdn_counts
    from horovod_tpu.models import llama_init

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"]) == (2, 8192, 1, "spmd")
    mod = child.load_file("models", "qwen3next")
    model = mod.Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.expert_width, c.n_heads, c.n_kv_heads, c.head_dim,
            c.vocab_size, c.n_layers, c.n_dense_layers, c.conv_taps,
            c.rope_theta, c.norm_eps, c.partial_rotary) == (
        2048, 512, 16, 2, 256, 18992, 4, 0, 4, 1e7, 1e-6, 64)
    assert (c.linear_key_heads, c.linear_value_heads, c.linear_key_dim,
            c.linear_value_dim) == (16, 32, 128, 128)
    assert (c.n_experts, c.n_experts_held, c.first_expert,
            c.n_experts_per_token, c.n_shared_experts) == (512, 32, 0, 10, 1)
    assert (c.score_func, c.norm_topk_prob, c.route_scale,
            c.moe_aux_weight) == ("softmax", True, 1.0, 0.0)
    assert c.qk_norm == "head" and c.attn_gate and c.shared_expert_gate \
        and c.rope_full_attention and c.moe_impl == "grouped" \
        and not (c.tie_embeddings or c.post_norm or c.scale_embed)
    assert [(s.stack, s.index) for s in c.layer_plan()] == [
        ("linear_layers", 0), ("linear_layers", 1), ("linear_layers", 2),
        ("layers", 0)]
    assert model.units_per_step == 16384 and model.even_share == 10240
    assert model.row_bound() == 20480
    # ISSUE 38's arithmetic: a Gated DeltaNet mixer 33.72 M, a gated
    # attention mixer 27.26 M, an expert 3.146 M, the router, the shared
    # expert and its gate 4.20 M, embedding and head 77.8 M: 625.7 M.
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "final_norm", "layers",
                              "linear_layers", "lm_head"]
    n = sum(x.size for x in jax.tree.leaves(shapes))
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128 \
        + 4096 * 2048 + 2048
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 + 2 * 256
    ffn = 2048 + 2048 * 512 + 3 * 2048 * 512 + 2048 \
        + 32 * 3 * 2048 * 512
    assert round(gdn / 1e6, 2) == 33.72 and round(attn / 1e6, 2) == 27.27
    assert n == 3 * (gdn + ffn) + (attn + ffn) + 2 * 18992 * 2048 + 2048
    assert round(n / 1e6, 1) == 625.7
    assert shapes["linear_layers"]["gdn_in"].shape == (3, 2048, 12288)
    assert shapes["linear_layers"]["gdn_conv"].shape == (3, 4, 8192)
    assert shapes["layers"]["wq"].shape == (1, 2048, 4096)
    # the counts: the matmul parameters a token passes at even routing
    # (10,240 rows of 16,384 tokens: 0.625 held experts a token)
    p = mod.matmul_params_per_token(c, 3, 1, 0.625)
    assert p == 3 * 2048 * (12288 + 64 + 4096) \
        + 2048 * 256 * (3 * 16 + 2 * 2) \
        + 4 * (2048 * 512 + 3 * 2048 * 512 + 2048
               + 0.625 * 3 * 2048 * 512) + 2048 * 18992
    flops, nbytes = model.gated_delta_rule_work()
    assert flops == 3 * 3 * 7 * 128 * 128 * 32 * 16384
    assert model.flops_per_unit() == 6 * p + 12 * 16 * 256 \
        * (8192 * 8193 // 2) / 8192 + flops / 16384
    # q, k at 16 heads, v, o at 32, two float32 gates a value head:
    # forward 24,832 B a token, backward 41,472
    assert nbytes == 3 * 16384 * (24832 + 41472)
    assert gdn_counts.rule_bytes(1, 16, 32, 128, 128, 1) == 24832 + 41472
    # bytes bind: 1.33 ms a layer against 0.92 ms of FLOPs
    floor = gdn_counts.floor_s("TPU v5 lite", flops, nbytes)
    assert floor == nbytes / 819e9 and round(floor * 1e3, 2) == 3.98
    assert round(flops / 197e12 * 1e3, 2) == 2.75
    gflops, gbytes = model.grouped_gemm_work()
    assert gflops == 4 * 18 * 10240 * 2048 * 512
    assert gbytes == 4 * 9 * 2 * (10240 * (2048 + 512) + 32 * 2048 * 512)


def _gdn_ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.gdn.proj`` (400 ns), the ``while`` and the gather in its body
    under ``hvd.gdn.core`` (400 + 400), the recomputed elementwise
    fusion under ``hvd.gdn.chain`` (100), over two steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in (("hvd.attn.proj", "hvd.gdn.proj"),
                         ("hvd.moe.dispatch", "hvd.gdn.core"),
                         ("hvd.ffn", "hvd.gdn.chain")):
            text = text.replace(old, new)
    ctx = t._ctx(monkeypatch, [("jit_hvd_grad", text),
                               ("jit_hvd_apply", t.APPLY)])
    ctx.model = model if model is not None else types.SimpleNamespace()
    return ctx


def test_the_gdn_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child

    # 96 ns of required work at the HBM peak, 2 FLOPs
    model = types.SimpleNamespace(
        gated_delta_rule_work=lambda: (2, 96e-9 * 819e9))
    ctx = _gdn_ctx(monkeypatch, model)
    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    assert read["gdn_proj_ms_per_step"](ctx) == pytest.approx(400 / 1e6 / 2)
    assert read["gdn_core_ms_per_step"](ctx) == pytest.approx(800 / 1e6 / 2)
    assert read["gdn_chain_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["gdn_core_roofline_pct"](ctx) == pytest.approx(
        100.0 * 96 / 400)
    # a model kind that counts no such work: nothing, and no exception
    assert read["gdn_core_roofline_pct"](_gdn_ctx(monkeypatch)) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_scope_reads_nothing(monkeypatch, metric):
    """A model with no such layer (the scopes of another), a program
    with no scope tables at all, and a program from before the scopes
    (its table does not know the name): None, never 0, no exception."""
    from chipbench import child, scopes

    model = types.SimpleNamespace(gated_delta_rule_work=lambda: (2, 96.0))
    read = child.load_reader(metric).read
    assert read(_gdn_ctx(monkeypatch, model, rename=False)) is None
    ctx = _gdn_ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None

    def before_the_scopes(ctx, *names, **_):
        raise ValueError(f"no device scopes: {names}")

    monkeypatch.setattr(scopes, "ms_per_step", before_the_scopes)
    assert read(_gdn_ctx(monkeypatch, model)) is None


TINY = {
    "kind": "qwen3next", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "rope_theta": 1e7, "rms_norm_eps": 1e-6, "num_experts": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "partial_rotary_factor": 0.25,
    "tie_word_embeddings": False, "mlp_only_layers": [],
    "decoder_sparse_step": 1, "layer_types": [L, L, L, A],
    "reduced": {"num_experts": {"published": 16, "here": 4}},
    "assumed": {"remat": "attn/ffn", "param_dtype": "float32",
                "first_expert": 4,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
LEAVES = {"embed", "final_norm", "lm_head", "gdn_norm", "gdn_in", "gdn_ba",
          "gdn_conv", "gdn_a_log", "gdn_dt_bias", "gdn_out_norm",
          "gdn_out", "attn_norm", "mlp_norm", "q_norm", "k_norm", "wq",
          "wk", "wv", "wg", "wo", "router", "moe_gate", "moe_up",
          "moe_down", "shared_gate", "shared_up", "shared_down",
          "shared_score"}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "qwen3next")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_qwen3next_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (flash,) = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert flash["shape"] == [2, 128, 4, 16] and flash["kv_heads"] == 2
    assert max(flash["err"].values()) < 6e-3
    (rule,) = [s for s in said
               if s["event"] == "delta_rule_vs_token_by_token"]
    assert rule["shape"] == [2, 128, 4, 16] and rule["key_dim"] == 16
    assert set(rule["err"]) == {"fwd", "dq", "dk", "dv", "dg", "dbeta"}
    # bf16 operands through two chunks
    assert max(rule["err"].values()) < 1.2e-2
    assert rule["required_flops_per_step"] == 3 * 21 * 16 * 16 * 4 * 256
    gmm = [s for s in said if s["event"] == "grouped_mm_vs_numpy"]
    assert [s["which"] for s in gmm] == ["gate_up", "down"]
    # 2 x 128 tokens x 3 choices x 4 of 16 held = 192 rows at even
    # routing; the bound is twice that
    assert all(s["shape"][0][0] == 384 and s["rows_in_groups"] == 192
               for s in gmm)
    load = [s for s in said if s["event"] == "expert_load"][0]
    assert load["on"] == "the batch trained on"
    assert load["rows_an_even_router_hands_this_chip"] == 192
    assert model.rows_held == load["rows_held_per_layer"]
    assert len(model.rows_held) == 4
    assert all(0 < rows < 768 for rows in model.rows_held)
    # the step: every leaf of the tree in both readings
    step = [s for s in said if s["event"] == "step_vs_reference"][0]
    assert (step["tokens"], step["on"]) == (256, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss"} | {"d_" + x for x in LEAVES} \
        | {"moved_" + x for x in LEAVES}
    # The lowering: a fault is reported, not swallowed.
    chunked = "tensor<2x2x4x64x16xf32>"
    kernels = " tpu_custom_call @gmm @tgmm hvd_flash_fwd " \
        + "stablehlo.triangular_solve " * 9
    assert model.check_lowering(chunked, False) is None
    assert model.check_lowering(chunked + kernels, True) is None
    assert "chunk-major" in model.check_lowering("", False)
    assert "scan over tokens" in model.check_lowering(
        chunked + " tensor<128x2x4x16xf32>", False)
    assert "triangular systems" in model.check_lowering(
        chunked + kernels.replace("stablehlo.triangular_solve ", "", 1),
        True)
    assert "@tgmm" in model.check_lowering(
        chunked + kernels.replace("@tgmm", ""), True)


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
    rule = [f for f in r["faults"] if f.startswith("delta rule")]
    assert len(rule) == 6                  # out and all five gradients


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, one layer at a
    time) and the program's (horovod_tpu/models/reference.py) agree on
    logits and loss to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import (
        qwen3next_forward,
        qwen3next_loss,
    )

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    p = mod.reference_params(params, c)
    got = jax.jit(lambda p: mod.reference_logits(p, tokens, c))(p)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p: qwen3next_forward(p, tokens, c))(params)
        ref_loss = qwen3next_loss(params, batch, c)
    assert float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))) < 2e-5
    loss = jax.jit(lambda p: mod.reference_loss(p, batch, c))(p)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
