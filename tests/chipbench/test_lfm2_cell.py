"""The LFM2-8B-A1B configuration, its counts, its reader and its adapter
on the CPU: published widths against the catalog, ``reduced``, the
counts against hand counts, ``short_conv_ms_per_step`` on recorded
events, ``child.measure`` through the adapter's whole ``check_outputs``
at a tiny size, faults planted in the step it compares, the fp8 control,
and the benchmark's reference against the program's."""

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

CELL = "lfm2moe.spmd.b2s8192"
C, A = "conv", "full_attention"
KEPT = [C, A, C, C, C, A, C, C, C]
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
# The catalog's `config` for LFM2-8B-A1B (the model-configs guide's
# architectures.jsonl), less the five reduced keys.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True}


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert list(cfg["reduced"]) == REDUCED
    here = {k: cfg["reduced"][k]["here"] for k in REDUCED}
    assert here == {k: cfg[k] for k in REDUCED} == {
        "num_hidden_layers": 9, "num_dense_layers": 1,
        "layer_types": KEPT, "num_experts": 8, "vocab_size": 16384}
    published = {k: cfg["reduced"][k]["published"] for k in REDUCED}
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (
        24, 2, 32, 65536)
    # the floors: whole periods and four layers at least after the dense
    # one, 8 routed experts at least, an eighth of the vocabulary
    assert cfg["layer_types"][1:] == [A, C, C, C] * 2
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 4 == 65536
    a = cfg["assumed"]
    assert a["shares_a_layer"] == 4 and "FOUR" in cfg["stands_for"]
    assert a["first_expert"] == 0 and a["remat"] and cfg["why"]
    assert "absent" in a["router_aux_loss"] and "zero" in a["expert_bias"]
    assert "tie_embedding" in a["why"] and "1e-6" in a["router_guard"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "spmd.b2s8192")
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "tokens_per_s", "step_ms_p90", "peak_hbm_gb", "setup_s",
        "device_idle_pct.lm", "optimizer_ms_per_step.lm",
        "spmd_dispatch_ms_per_step.lm", "flash_bwd_ms_per_step",
        "setup_compile_s", "moe_gmm_ms_per_step", "moe_gmm_roofline_pct",
        "moe_dispatch_ms_per_step", "short_conv_ms_per_step"}
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] == "short_conv_ms_per_step"] == [[CELL]]


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import child
    from horovod_tpu.models import llama_init

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"]) == (2, 8192, 1, "spmd")
    mod = child.load_file("models", "lfm2moe")
    model = mod.Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.d_ff, c.expert_width, c.n_heads, c.n_kv_heads,
            c.head_dim, c.vocab_size, c.n_layers, c.n_dense_layers,
            c.conv_taps, c.rope_theta) == (
        2048, 7168, 1792, 32, 8, 64, 16384, 9, 1, 3, 1e6)
    assert (c.n_experts, c.n_experts_held, c.first_expert,
            c.n_experts_per_token, c.n_shared_experts) == (32, 8, 0, 4, 0)
    assert (c.score_func, c.norm_topk_prob, c.route_scale,
            c.moe_aux_weight) == ("sigmoid", True, 1, 0.0)
    assert c.qk_norm == "head" and c.tie_embeddings \
        and c.rope_full_attention and c.moe_impl == "grouped" \
        and not (c.attn_gate or c.post_norm or c.scale_embed)
    assert [(s.stack, s.index) for s in c.layer_plan()] == [
        ("dense_conv_layers", 0), ("layers", 0), ("conv_layers", 0),
        ("conv_layers", 1), ("conv_layers", 2), ("layers", 1),
        ("conv_layers", 3), ("conv_layers", 4), ("conv_layers", 5)]
    assert model.units_per_step == 16384 and model.even_share == 16384
    assert model.row_bound() == 32768
    # ISSUE 34's arithmetic: a conv mixer 16.78 M, an attention one
    # 10.49 M, an expert 11.01 M, the router 65.5 k, the dense FFN
    # 44.04 M, the tied matrix 33.6 M: 921 M parameters.
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["conv_layers", "dense_conv_layers", "embed",
                              "final_norm", "layers"]
    n = sum(x.size for x in jax.tree.leaves(shapes))
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 2048 + 2 * 64
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert n == (conv + 3 * 2048 * 7168) + 6 * (conv + experts) \
        + 2 * (attn + experts) + 16384 * 2048 + 2048
    assert round(n / 1e6, 1) == 921.3
    assert shapes["conv_layers"]["expert_bias"].dtype == "float32"
    assert shapes["conv_layers"]["conv_w"].shape == (6, 3, 2048)
    # the counts: 304.6 M matmul parameters a token at even routing
    # (seven conv mixers: the dense layer's is one)
    rows = 16384 / 16384
    p = mod.matmul_params_per_token(c, 7, 2, rows)
    assert p == 7 * 4 * 2048 ** 2 + 2 * 2048 * 64 * 80 + 3 * 2048 * 7168 \
        + 8 * (2048 * 32 + 3 * 2048 * 1792) + 2048 * 16384
    assert round(p / 1e6, 1) == 304.6
    assert model.flops_per_unit() == 6 * p + 2 * 12 * 32 * 64 \
        * (8192 * 8193 // 2) / 8192
    # the convolution chains: 11 d elements a token and layer, 7 layers
    assert mod.short_conv_bytes(16384, 2048, 7) == 7 * 16384 * 11 * 2048 * 2
    assert round(model.short_conv_floor_s("TPU v5 lite") * 1e3, 2) == 6.31
    flops, nbytes = model.grouped_gemm_work()
    assert flops == 8 * 18 * 16384 * 2048 * 1792
    assert nbytes == 8 * 9 * 2 * (16384 * (2048 + 1792)
                                  + 8 * 2048 * 1792)


# Two whole steps [1000, 3000]; in each, as the v5e's trace names them
# (PR 34): a fusion of the conv chain, the in-projection that feeds it,
# the assembly of the stacked ``conv_in`` gradient and a Mosaic call.
_L = "{2,1,0:T(8,128)(2,1)}"
EVENTS = {
    "CONV": f"%slice_multiply_fusion.8 = bf16[2,8192,2048]{_L} fusion("
            f"bf16[2,8192,6144]{_L} %fusion.3197, bf16[3,2048]{{1,0:T(4,128)"
            "(2,1)} %bitcast.7), kind=kLoop, calls=%fused_computation.4871",
    "MATMUL": f"%fusion.3197 = bf16[2,8192,6144]{_L} fusion(bf16[2,8192,"
              f"2048]{_L} %remat2.184, f32[2,8192]{{1,0:T(2,128)}} "
              "%add_rsqrt_fusion.5, bf16[2048]{0:T(1024)(128)(2,1)} "
              f"%remat2.186, bf16[6,2048,6144]{_L} "
              "%p__conv_layers____conv_in__.1), kind=kOutput, "
              "calls=%fused_computation.4454",
    "STACKED": f"%pad_add_fusion.115 = bf16[6,2048,6144]{_L} fusion("
               f"bf16[1,2048,6144]{_L} %fusion.3493, bf16[1,2048,6144]{_L} "
               "%fusion.3496), kind=kLoop, calls=%fused_computation.5012",
    "MOSAIC": "%tpu_custom_call.4 = bf16[64,128]{1,0:T(8,128)(2,1)} "
              "custom-call(bf16[64,128]{1,0:T(8,128)(2,1)} %p.4), "
              'custom_call_target=\\"tpu_custom_call\\"',
}
XSPACE = r"""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 10 offset_ps: 0 duration_ps: 900000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 900000 }
    events { metadata_id: 10 offset_ps: 2000000 duration_ps: 900000 }
    events { metadata_id: 10 offset_ps: 3000000 duration_ps: 900000 }
  }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 1100000 duration_ps: 170000 }
    events { metadata_id: 3 offset_ps: 1300000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 300000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2100000 duration_ps: 170000 }
    events { metadata_id: 3 offset_ps: 2300000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 2500000 duration_ps: 300000 }
  }
  event_metadata { key: 1 value { id: 1 name: "CONV" } }
  event_metadata { key: 2 value { id: 2 name: "MATMUL" } }
  event_metadata { key: 3 value { id: 3 name: "STACKED" } }
  event_metadata { key: 4 value { id: 4 name: "MOSAIC" } }
  event_metadata { key: 10 value { id: 10 name: "jit_hvd_grad(1)" } }
}
"""


def _chip(**other):
    from jax.profiler import ProfileData

    from chipbench import xplane

    text = XSPACE
    for key, event in dict(EVENTS, **other).items():
        text = text.replace(f'"{key}"', f'"{event}"')
    (chip,) = xplane.chips(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)
    return chip


def test_short_conv_reader_finds_the_chain_by_the_type_only_it_touches():
    from chipbench import child, xplane

    read = child.load_reader("short_conv_ms_per_step").read
    model = types.SimpleNamespace(
        cfg=types.SimpleNamespace(conv_taps=3, d_model=2048),
        batch_size=2, seq=8192)
    chip = _chip()
    assert [xplane.opcode(e) for e in chip.ops[:4]] == [
        "fusion", "fusion", "fusion", "custom-call"]
    ctx = types.SimpleNamespace(chip=chip, model=model)
    # the chain's fusion alone: not the projection (kOutput), not the
    # stacked weights' gradient (kLoop, but no activation), not a kernel
    assert read(ctx) == pytest.approx(100 / 1e6)
    assert child.load_reader("moe_gmm_ms_per_step").read(ctx) \
        == pytest.approx(300 / 1e6)
    # a model of another width or size, a model with no conv layer, a
    # model kind with no ``cfg``: nothing, not zero, and no exception
    for other in (
            types.SimpleNamespace(cfg=types.SimpleNamespace(
                conv_taps=3, d_model=1024), batch_size=2, seq=8192),
            types.SimpleNamespace(cfg=types.SimpleNamespace(
                conv_taps=0, d_model=2048), batch_size=2, seq=8192),
            types.SimpleNamespace(cfg=types.SimpleNamespace(d_model=2048),
                                  batch_size=2, seq=8192),
            types.SimpleNamespace()):
        assert read(types.SimpleNamespace(chip=chip, model=other)) is None
    # a chain the compiler emitted under another kind is not found
    folded = _chip(CONV=EVENTS["CONV"].replace("kLoop", "kOutput"))
    assert read(types.SimpleNamespace(chip=folded, model=model)) is None


TINY_LFM2 = {
    "kind": "lfm2moe", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "moe_intermediate_size": 32, "rope_theta": 1e6, "norm_eps": 1e-5,
    "num_experts": 2, "num_experts_per_tok": 4, "num_dense_layers": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "conv_L_cache": 3, "conv_bias": False, "use_expert_bias": True,
    "layer_types": [C, A, C, C, C],
    "reduced": {"num_experts": {"published": 8, "here": 2}},
    "assumed": {"remat": "attn", "param_dtype": "float32",
                "first_expert": 2,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}


def _tiny():
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "lfm2moe")
    model = mod.Model(TINY_LFM2, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_lfm2_adapter_checks_every_comparison():
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
    # bf16 operands whatever the model computes in: one rounding of the
    # result (4e-3), inside the chip's bounds
    assert max(flash["err"].values()) < 6e-3
    (conv,) = [s for s in said if s["event"] == "short_conv_vs_three_taps"]
    assert conv["shape"] == [2, 128, 192] and conv["taps"] == 3
    assert set(conv["err"]) == {"fwd", "dB", "dC", "dz", "dw"}
    assert max(conv["err"].values()) < 8e-3
    assert conv["required_bytes_per_step"] == 4 * 256 * 11 * 64 * 2
    gmm = [s for s in said if s["event"] == "grouped_mm_vs_numpy"]
    assert [s["which"] for s in gmm] == ["gate_up", "down"]
    # 2 x 128 tokens x 4 choices x 2 of 8 held = 256 rows at even
    # routing; the bound is twice that; the groups cover about half of it
    assert all(s["shape"][0][0] == 512 and s["rows_in_groups"] == 256
               for s in gmm)
    load = [s for s in said if s["event"] == "expert_load"][0]
    assert load["on"] == "the batch trained on"
    assert load["rows_an_even_router_hands_this_chip"] == 256
    assert load["row_bound"] == 512
    assert model.rows_held == load["rows_held_per_layer"]
    assert len(model.rows_held) == 4
    assert all(0 < rows < 1024 for rows in model.rows_held)
    flops, _ = model.grouped_gemm_work()
    assert flops == 18 * sum(model.rows_held) * 64 * 32
    # the step: on the batch the run trained on, every leaf of the tree
    # in both readings, the tied matrix once
    step = [s for s in said if s["event"] == "step_vs_reference"][0]
    assert (step["tokens"], step["on"]) == (256, "the batch trained on")
    assert max(step["err"].values()) < 2e-4, step
    leaves = {"embed", "final_norm", "conv_norm", "conv_in", "conv_w",
              "conv_out", "attn_norm", "mlp_norm", "q_norm", "k_norm",
              "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router",
              "expert_bias", "moe_gate", "moe_up", "moe_down"}
    assert set(step["err"]) == {"loss"} | {"d_" + x for x in leaves} \
        | {"moved_" + x for x in leaves}
    # A fault is reported, not swallowed.
    both = "tpu_custom_call @gmm @tgmm hvd_flash_fwd"
    assert model.check_lowering(both, True) is None
    assert model.check_lowering("", False) is None
    assert "hvd_flash_fwd" in model.check_lowering(
        both.replace("hvd_flash_fwd", ""), True)
    assert "@tgmm" in model.check_lowering(both.replace("@tgmm", ""), True)


@pytest.fixture(scope="module")
def trained():
    """A tiny model a few steps into its fixed batch: (module, model,
    its parameters, the batch)."""
    import jax
    import jax.numpy as jnp

    child, mod, lane, model = _tiny()
    step, carry, batch, _ = lane.build(model, child.key_of(2 ** 31 + 9), {})
    for _ in range(3):
        _, carry = step(carry, batch)
    params = lane.params_of(carry)
    jax.block_until_ready(params)
    assert (model.trained_on == jnp.asarray(batch["tokens"])).all()
    return mod, model, params, dict(batch)


# What the comparison of the step has to refuse, and by which readings
# at least: faults planted in the program's place.
PLANTED = {
    "nothing": set(),
    "one batch row twice": {"d_", "loss"},
    "adam at twice the rate": {"moved_"},
    "an aux term in the loss": {"loss", "d_"},
    "the token after the next as target": {"loss", "d_"},
    "the taps a position late": {"loss", "d_"},
    "a head of its own": {"d_"},
}
NOTHING_ELSE = {"nothing", "adam at twice the rate"}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_the_step_comparison_refuses_a_planted_fault(trained, fault):
    import copy

    import jax.numpy as jnp

    mod, model, params, batch = trained
    planted, fed, held = copy.copy(model), batch, params
    if fault == "one batch row twice":
        fed = {k: jnp.stack([v[0], v[0]]) for k, v in batch.items()}
    elif fault == "adam at twice the rate":
        planted.opt = dict(model.opt,
                           learning_rate=2 * model.opt["learning_rate"])
    elif fault == "an aux term in the loss":
        planted.cfg = dataclasses.replace(model.cfg, moe_aux_weight=0.05)
    elif fault == "the token after the next as target":
        fed = dict(batch, targets=jnp.roll(batch["targets"], -1, 1))
    elif fault == "the taps a position late":
        # w_j meets u_{t-3+j}: a program that shifted once too often
        held = dict(params, **{s: dict(params[s], conv_w=jnp.roll(
            params[s]["conv_w"], 1, 1).at[:, 0].set(0.0))
            for s in ("conv_layers", "dense_conv_layers")})
    said = []
    got = planted._step_readings(held, fed, lambda **k: None)
    if fault == "a head of its own":
        # the head's gradient left out of the tied matrix's: what an
        # untied program would hand back for ``embed``
        import jax

        from horovod_tpu.models import llama_loss

        untied = dataclasses.replace(model.cfg, tie_embeddings=False)
        apart = jax.grad(llama_loss)(
            dict(params, lm_head=params["embed"].T), batch, untied)
        got["grads"] = dict(got["grads"], embed=apart["embed"])
    faults = model._check_step(params, batch, got,
                               lambda **k: said.append(k))
    kinds = {k for k in ("loss", "d_", "moved_")
             if any(f.startswith("the step's " + k) for f in faults)}
    assert kinds >= PLANTED[fault], faults
    assert kinds == PLANTED[fault] or fault not in NOTHING_ELSE, faults
    assert all(f.startswith("the step's ") for f in faults)
    if fault == "a head of its own":
        assert [f for f in faults if "d_embed" in f] == faults
    assert [s["event"] for s in said] == ["expert_load",
                                          "step_vs_reference"]


def test_fp8_in_the_programs_place_is_refused_by_every_comparison(
        monkeypatch, capsys):
    """The control as the chip runs it (``python3 -m
    chipbench.models.lfm2moe --seed N``), on a tiny cell: the run as
    ever, the reference computed in fp8 where the comparisons read the
    program. Each of the four has to refuse it, by the chip's own
    bounds."""
    from chipbench import child
    from chipbench.models import lfm2moe
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(child, "find_cell", lambda name: (
        None, None, TINY_LFM2, TINY_TRAFFIC))
    # tests keep the compile cache off
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    assert lfm2moe.main(["--seed", str(2 ** 31 + 11)]) == 0
    said = [json.loads(line) for line in capsys.readouterr().out.split("\n")
            if line.startswith("{")]
    refused = said[-1]["fp8_refused_by"]
    assert set(refused) == {"flash", "short convolution", "grouped GEMM",
                            "the step"}
    for kind in ("flash fwd", "flash dq", "short convolution fwd",
                 "short convolution dw", "grouped GEMM gate_up",
                 "grouped GEMM down", "the step's d_"):
        assert any(f.startswith(kind) for fs in refused.values()
                   for f in fs), (kind, refused)
    assert "the_reference_in_fp8_in_the_programs_place" in [
        s["event"] for s in said]


def test_the_benchmarks_reference_is_the_programs_reference(monkeypatch):
    """Two copies by design (the benchmark's may not move with the
    program); on the same weights they give the same numbers: whole, and
    a layer at a time in blocks as the chip's comparison runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import child
    from chipbench.models import afmoe
    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import lfm2_forward, lfm2_loss

    mod = child.load_file("models", "lfm2moe")
    model = mod.Model(TINY_LFM2, TINY_TRAFFIC)
    c = dataclasses.replace(model.cfg, dtype="float32")
    params = llama_init(c, jax.random.PRNGKey(3))
    for stack in ("conv_layers", "layers"):
        params[stack]["expert_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(5), params[stack]["expert_bias"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                                c.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    p = mod.reference_params(params, c)
    whole = mod.reference_logits(p, tokens, c)
    np.testing.assert_allclose(whole, lfm2_forward(params, tokens, c),
                               rtol=1e-5, atol=1e-5)
    loss, grads = jax.value_and_grad(lfm2_loss)(params, batch, c)
    np.testing.assert_allclose(mod.reference_loss(p, batch, c), loss,
                               rtol=1e-6)
    # four blocks of query rows, four of tokens
    monkeypatch.setattr(afmoe, "ATTENTION_BLOCK_ROWS", 16)
    monkeypatch.setattr(mod, "TOKEN_BLOCK", 32)
    monkeypatch.setattr(afmoe, "TOKEN_BLOCK", 32)
    np.testing.assert_allclose(mod.reference_logits(p, tokens, c), whole,
                               rtol=1e-5, atol=1e-5)
    seen = {}
    in_blocks, loads = mod.reference_loss_and_grads(
        params, batch, c,
        lambda where, ref: seen.setdefault(where, {}).update(ref))
    np.testing.assert_allclose(in_blocks, loss, rtol=1e-6)
    assert sorted(seen) == [(), ("conv_layers", 0), ("conv_layers", 1),
                            ("conv_layers", 2), ("dense_conv_layers", 0),
                            ("layers", 0)]
    for where, ref in seen.items():
        for name, r in ref.items():
            g = grads[where[0]][name][where[1]] if where else grads[name]
            np.testing.assert_allclose(
                r, g, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(g)))
                + 1e-12, err_msg=f"{where} {name}")
    # every leaf of the tree was handed over, the tied matrix once
    assert set(seen[()]) == {"embed", "final_norm"}
    assert all(set(ref) == set(params[where[0]])
               for where, ref in seen.items() if where)
    # the rows the router hands the held experts: all 2 x 64 x 4 slots
    # when every expert is held
    assert np.asarray(loads).shape == (4, 2)
    everyone = dataclasses.replace(c, first_expert=0, n_experts_held=0)
    full = llama_init(everyone, jax.random.PRNGKey(3))
    _, loads = mod.reference_loss_and_grads(
        full, batch, everyone, lambda where, ref: None)
    assert [float(x.sum()) for x in loads] == [2 * 64 * 4] * 4
