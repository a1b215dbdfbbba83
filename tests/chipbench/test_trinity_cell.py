"""The Trinity-Mini configuration, its counts, its readers and its
adapter on the CPU: published widths against the catalog, ``reduced``,
the arithmetic of ``afmoe_counts.py`` against hand counts, the window
readers on a hand-built trace, ``child.measure`` through the adapter's
whole ``check_outputs`` at a tiny size, faults planted in the step it
compares, and the fp8 control."""

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

CELL = "trinitymini.spmd.b2s8192"
S, F = "sliding_attention", "full_attention"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
# The catalog's `config` for Trinity-Mini (the model-configs guide's
# architectures.jsonl), less the five reduced keys.
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_expert_groups": 1, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert list(cfg["reduced"]) == REDUCED
    here = {k: cfg["reduced"][k]["here"] for k in REDUCED}
    assert here == {k: cfg[k] for k in REDUCED} == {
        "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": [S, S, S, S, F], "num_experts": 16,
        "vocab_size": 25024}
    published = {k: cfg["reduced"][k]["published"] for k in REDUCED}
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (
        32, 2, 128, 200192)
    # the floors: a whole period and four layers after the dense one, 8
    # routed experts at least, an eighth of the vocabulary
    assert cfg["layer_types"][1:] == [S, S, S, F]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 == 200192
    a = cfg["assumed"]
    assert a["shares_a_layer"] == 8 and "EIGHT" in cfg["stands_for"]
    assert a["first_expert"] == 0 and a["remat"] and cfg["why"]
    assert "absent" in a["router_aux_loss"] and "zero" in a["expert_bias"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == "trinity-mini"][0]
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
        "moe_dispatch_ms_per_step", "flash_window_ms_per_step",
        "flash_window_roofline_pct"}


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import child
    from horovod_tpu.models import llama_init

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"]) == (2, 8192, 1, "spmd")
    model = child.load_file("models", "afmoe").Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.d_ff, c.expert_width, c.n_heads, c.n_kv_heads,
            c.head_dim, c.vocab_size, c.n_layers, c.n_dense_layers) == (
        2048, 6144, 1024, 32, 4, 128, 25024, 5, 1)
    assert (c.n_experts, c.n_experts_held, c.first_expert,
            c.n_experts_per_token, c.n_shared_experts) == (128, 16, 0, 8, 1)
    assert (c.score_func, c.norm_topk_prob, c.route_scale,
            c.sliding_window, c.moe_aux_weight) == ("sigmoid", True, 2.826,
                                                    2048, 0.0)
    assert c.qk_norm == "head" and c.attn_gate and c.post_norm \
        and c.scale_embed and c.moe_impl == "grouped"
    assert c.layer_kinds() == [(True, 2048, True)] \
        + [(False, 2048, True)] * 3 + [(False, 0, False)]
    assert model.units_per_step == 16384 and model.even_share == 16384
    assert model.row_bound() == 32768
    # ISSUE 32's arithmetic: attention 27.26 M a layer, an expert 6.29 M,
    # an expert layer 134.5 M, the dense layer 65.0 M, embedding + head
    # 102.5 M: 705.5 M parameters.
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 4 * 2048 + 2 * 128
    expert = 3 * 2048 * 1024
    assert n == (attn + 3 * 2048 * 6144) \
        + 4 * (attn + 17 * expert + 2048 * 128 + 128) \
        + 2 * 25024 * 2048 + 2048
    assert round(n / 1e6, 1) == 705.5
    assert shapes["layers"]["expert_bias"].dtype == "float32"


def test_required_work_is_counted_from_shapes_and_rows_held():
    from chipbench import afmoe_counts as ac

    # the band's visible pairs, by formula and by brute force
    assert ac.visible_pairs(8192, 2048) == 8192 * 2048 - 2048 * 2047 // 2
    assert ac.visible_pairs(8192) == 8192 * 8193 // 2
    for t, w in ((96, 5), (96, 40), (96, 96), (96, 500), (7, 1)):
        brute = sum(1 for i in range(t) for j in range(t)
                    if j <= i and j > i - w)
        assert ac.visible_pairs(t, w) == brute
    # 276.7 M matmul parameters a token at even routing (ISSUE 32: 276.6)
    n = ac.matmul_params_per_token(2048, 6144, 1024, 32, 4, 128, 1, 4,
                                   25024, 128, 1, 1.0)
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert n == 5 * attn + 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + 2 * 3 * 2048 * 1024) + 2048 * 25024
    assert round(n / 1e6, 1) == 276.7
    windows = [2048, 2048, 2048, 2048, 0]
    flops = ac.train_flops_per_token(n, 8192, 32, 128, windows)
    pairs = 4 * ac.visible_pairs(8192, 2048) + ac.visible_pairs(8192)
    assert flops == 6 * n + 12 * 32 * 128 * pairs / 8192
    assert round(flops / 1e9, 2) == 2.21        # 1.66 + 0.55
    # one window layer's flash calls: 1.44e12 FLOPs, 7.3 ms at the peak
    a = ac.attention_flops(2, 8192, 32, 128, 2048)
    assert a == 12 * 2 * 32 * 128 * ac.visible_pairs(8192, 2048)
    nbytes = ac.attention_bytes(2, 8192, 32, 4, 128)
    assert nbytes == 6 * 2 * 8192 * (32 + 4) * 128 * 2
    assert round(ac.floor_s("TPU v5 lite", a, nbytes) * 1e3, 1) == 7.3
    assert round(nbytes / 819e9 * 1e3, 1) == 1.1
    # the grouped GEMMs follow the rows HELD, a layer
    rows = [16384, 15000, 20000, 1]
    assert ac.grouped_gemm_flops(rows, 2048, 1024) \
        == 18 * sum(rows) * 2048 * 1024
    assert ac.grouped_gemm_bytes(rows, 2048, 1024, 16) == sum(
        9 * 2 * (r * 3072 + 16 * 2048 * 1024) for r in rows)
    with pytest.raises(KeyError):
        ac.floor_s("cpu", 1.0, 1.0)


# Two whole steps [1000, 3000]; in each a flash call with a window, one
# without, a backward call with a window and a grouped GEMM.
_CALL = ('%tpu_custom_call.{n} = bf16[64,128]{{1,0:T(8,128)(2,1)}} '
         'custom-call(bf16[64,128]{{1,0:T(8,128)(2,1)}} %p.{n}), '
         'custom_call_target=\\"tpu_custom_call\\"{extra}')
_META = (', frontend_attributes={{kernel_metadata={{\\n\\"kernel\\":'
         '\\"{name}\\"{window}\\n}}}}')
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
    events { metadata_id: 3 offset_ps: 1300000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 300000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2100000 duration_ps: 170000 }
    events { metadata_id: 3 offset_ps: 2300000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 2500000 duration_ps: 300000 }
  }
  event_metadata { key: 1 value { id: 1 name: "WFWD" } }
  event_metadata { key: 2 value { id: 2 name: "FULL" } }
  event_metadata { key: 3 value { id: 3 name: "WBWD" } }
  event_metadata { key: 4 value { id: 4 name: "GMM" } }
  event_metadata { key: 10 value { id: 10 name: "jit_hvd_grad(1)" } }
}
""".replace("WFWD", _CALL.format(n=1, extra=_META.format(
    name="hvd_flash_fwd", window=',\\n\\"window\\":\\"2048\\"'))).replace(
    "FULL", _CALL.format(n=2, extra=_META.format(
        name="hvd_flash_fwd", window=""))).replace(
    "WBWD", _CALL.format(n=3, extra=_META.format(
        name="hvd_flash_bwd_fused",
        window=',\\n\\"window\\":\\"2048\\"'))).replace(
    "GMM", _CALL.format(n=4, extra=""))


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData

    from chipbench import xplane

    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    (chip,) = xplane.chips(profile)
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)
    return chip


def test_window_readers_tell_the_windowed_calls_from_the_rest(
        chip, monkeypatch):
    import jax

    from chipbench import child

    model = types.SimpleNamespace(
        flash_window_work=lambda: (197e12 * 120e-9, 1.0))
    ctx = types.SimpleNamespace(chip=chip, model=model)
    assert child.load_reader("flash_window_ms_per_step").read(ctx) \
        == pytest.approx(300 / 1e6)          # forward + backward, windowed
    assert child.load_reader("flash_bwd_ms_per_step").read(ctx) \
        == pytest.approx(200 / 1e6)          # found by its prefix still
    assert child.load_reader("flash_ms_per_step").read(ctx) \
        == pytest.approx(770 / 1e6)          # every Mosaic call
    assert child.load_reader("moe_gmm_ms_per_step").read(ctx) \
        == pytest.approx(300 / 1e6)          # no flash call, windowed or not
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    # 120 ns of required work at peak over 300 ns of kernel time
    assert child.load_reader("flash_window_roofline_pct").read(ctx) \
        == pytest.approx(40.0)
    # a model kind that counts no such work, a program without a window
    # (the parent's): nothing, not zero, and no exception
    other = types.SimpleNamespace(chip=chip, model=types.SimpleNamespace())
    assert child.load_reader("flash_window_roofline_pct").read(other) \
        is None
    from jax.profiler import ProfileData

    from chipbench import xplane

    (plain,) = xplane.chips(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            XSPACE.replace(',\\n\\"window\\":\\"2048\\"', ""))))
    ctx = types.SimpleNamespace(chip=plain, model=model)
    assert child.load_reader("flash_window_ms_per_step").read(ctx) is None
    assert child.load_reader("flash_window_roofline_pct").read(ctx) is None


TINY_TRINITY = {
    "kind": "afmoe", "vocab_size": 128, "hidden_size": 64, "head_dim": 32,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "moe_intermediate_size": 32, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "num_experts": 4, "num_experts_per_tok": 4,
    "num_dense_layers": 1, "num_shared_experts": 1, "sliding_window": 48,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "mup_enabled": True, "layer_types": [S, S, S, S, F],
    "reduced": {"num_experts": {"published": 16, "here": 4}},
    "assumed": {"remat": "attn", "param_dtype": "float32",
                "first_expert": 4,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}


def _tiny():
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    afmoe = child.load_file("models", "afmoe")
    model = afmoe.Model(TINY_TRINITY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, afmoe, lane, model


def test_measure_with_a_tiny_afmoe_adapter_checks_every_comparison():
    child, afmoe, lane, model = _tiny()
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    flash = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert [s["window"] for s in flash] == [48, 0]
    # bf16 operands whatever the model computes in: one rounding of the
    # result (4e-3), inside the chip's bounds
    assert all(max(s["err"].values()) < 6e-3 for s in flash)
    gmm = [s for s in said if s["event"] == "grouped_mm_vs_numpy"]
    assert [s["which"] for s in gmm] == ["gate_up", "down"]
    # 2 x 128 tokens x 4 choices x 4 of 16 held = 256 rows at even
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
    # the counts follow the rows held
    flops, _ = model.grouped_gemm_work()
    assert flops == 18 * sum(model.rows_held) * 64 * 32
    # the step: on the batch the run trained on, 2 x 128 tokens, every
    # leaf of the tree in both readings
    step = [s for s in said if s["event"] == "step_vs_reference"][0]
    assert (step["tokens"], step["on"]) == (256, "the batch trained on")
    assert max(step["err"].values()) < 2e-4, step
    leaves = {"embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
              "post_attn_norm", "post_mlp_norm", "q_norm", "k_norm", "wq",
              "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down", "router",
              "expert_bias", "moe_gate", "moe_up", "moe_down",
              "shared_gate", "shared_up", "shared_down"}
    assert set(step["err"]) == {"loss"} | {"d_" + x for x in leaves} \
        | {"moved_" + x for x in leaves}
    # A fault is reported, not swallowed.
    meta = 'kernel_metadata = "{\\0A\\22kernel\\22:\\22hvd_flash_fwd\\22%s}"'
    both = "tpu_custom_call @gmm @tgmm " + meta % "" \
        + meta % ",\\0A\\22window\\22:\\222048\\22\\0A"
    assert model.check_lowering(both, True) is None
    assert "without a window" in model.check_lowering(
        both.replace(meta % "", ""), True)
    assert "with a window" in model.check_lowering(
        "tpu_custom_call @gmm @tgmm " + meta % "", True)
    assert "@tgmm" in model.check_lowering(both.replace("@tgmm", ""), True)


@pytest.fixture(scope="module")
def trained():
    """A tiny model a few steps into its fixed batch: (module, model,
    its parameters, the batch)."""
    import jax
    import jax.numpy as jnp

    child, afmoe, lane, model = _tiny()
    step, carry, batch, _ = lane.build(model, child.key_of(2 ** 31 + 9), {})
    for _ in range(3):
        _, carry = step(carry, batch)
    params = lane.params_of(carry)
    jax.block_until_ready(params)
    assert (model.trained_on == jnp.asarray(batch["tokens"])).all()
    return afmoe, model, params, dict(batch)


# What the comparison of the step has to refuse, and by which readings
# at least (a fault in the batch also moves the embedding rows of
# tokens it left out): faults planted in the program's place.
PLANTED = {
    "nothing": set(),
    "one batch row twice": {"d_", "loss"},
    "adam at twice the rate": {"moved_"},
    "an aux term in the loss": {"loss", "d_"},
    "the token after the next as target": {"loss", "d_"},
}
NOTHING_ELSE = {"nothing", "adam at twice the rate"}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_the_step_comparison_refuses_a_planted_fault(trained, fault):
    import copy

    import jax.numpy as jnp

    afmoe, model, params, batch = trained
    planted, fed = copy.copy(model), batch
    if fault == "one batch row twice":
        fed = {k: jnp.stack([v[0], v[0]]) for k, v in batch.items()}
    elif fault == "adam at twice the rate":
        planted.opt = dict(model.opt,
                           learning_rate=2 * model.opt["learning_rate"])
    elif fault == "an aux term in the loss":
        planted.cfg = dataclasses.replace(model.cfg, moe_aux_weight=0.05)
    elif fault == "the token after the next as target":
        fed = dict(batch, targets=jnp.roll(batch["targets"], -1, 1))
    said = []
    got = planted._step_readings(params, fed, lambda **k: None)
    faults = model._check_step(params, batch, got,
                               lambda **k: said.append(k))
    kinds = {k for k in ("loss", "d_", "moved_")
             if any(f.startswith("the step's " + k) for f in faults)}
    assert kinds >= PLANTED[fault], faults
    assert kinds == PLANTED[fault] or fault not in NOTHING_ELSE, faults
    assert all(f.startswith("the step's ") for f in faults)
    assert [s["event"] for s in said] == ["expert_load",
                                          "step_vs_reference"]


def test_fp8_in_the_programs_place_is_refused_by_every_comparison(
        monkeypatch, capsys):
    """The control as the chip runs it (``python3 -m
    chipbench.models.afmoe --seed N``), on a tiny cell: the run as ever,
    the reference computed in fp8 where the comparisons read the
    program. Each of the three has to refuse it, by the chip's own
    bounds."""
    from chipbench import child
    from chipbench.models import afmoe
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(child, "find_cell", lambda name: (
        None, None, TINY_TRINITY, TINY_TRAFFIC))
    # tests keep the compile cache off
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    assert afmoe.main(["--seed", str(2 ** 31 + 11)]) == 0
    said = [json.loads(line) for line in capsys.readouterr().out.split("\n")
            if line.startswith("{")]
    refused = said[-1]["fp8_refused_by"]
    assert set(refused) == {"flash", "grouped GEMM", "the step"}
    for kind in ("flash (window 48)", "flash (window 0)",
                 "grouped GEMM gate_up", "grouped GEMM down",
                 "the step's d_"):
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
    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import afmoe_forward, afmoe_loss

    afmoe = child.load_file("models", "afmoe")
    model = afmoe.Model(TINY_TRINITY, TINY_TRAFFIC)
    c = dataclasses.replace(model.cfg, dtype="float32")
    params = llama_init(c, jax.random.PRNGKey(3))
    params["layers"]["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), params["layers"]["expert_bias"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                                c.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    p = afmoe.reference_params(params)
    whole = afmoe.reference_logits(p, tokens, c)
    np.testing.assert_allclose(whole, afmoe_forward(params, tokens, c),
                               rtol=1e-5, atol=1e-5)
    loss, grads = jax.value_and_grad(afmoe_loss)(params, batch, c)
    np.testing.assert_allclose(afmoe.reference_loss(p, batch, c), loss,
                               rtol=1e-6)
    # four blocks of query rows (16 rows against 64 keys, the window of
    # 48 binds in the last), four of tokens
    monkeypatch.setattr(afmoe, "ATTENTION_BLOCK_ROWS", 16)
    monkeypatch.setattr(afmoe, "TOKEN_BLOCK", 32)
    np.testing.assert_allclose(afmoe.reference_logits(p, tokens, c), whole,
                               rtol=1e-5, atol=1e-5)
    seen = {}
    in_blocks, loads = afmoe.reference_loss_and_grads(
        params, batch, c,
        lambda where, ref: seen.setdefault(where, {}).update(ref))
    np.testing.assert_allclose(in_blocks, loss, rtol=1e-6)
    assert sorted(seen) == [(), ("dense_layers", 0)] + [
        ("layers", i) for i in range(4)]
    for where, ref in seen.items():
        for name, r in ref.items():
            g = grads[where[0]][name][where[1]] if where else grads[name]
            np.testing.assert_allclose(
                r, g, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(g)))
                + 1e-12, err_msg=f"{where} {name}")
    # every leaf of the tree was handed over
    assert set(seen[()]) == {"embed", "final_norm", "lm_head"}
    assert all(set(ref) == set(params[where[0]])
               for where, ref in seen.items() if where)
    # the rows the router hands the held experts: all 2 x 64 x 4 slots
    # when every expert is held
    assert np.asarray(loads).shape == (4, 4)
    everyone = dataclasses.replace(c, first_expert=0, n_experts_held=0)
    full = llama_init(everyone, jax.random.PRNGKey(3))
    _, loads = afmoe.reference_loss_and_grads(
        full, batch, everyone, lambda where, ref: None)
    assert [float(x.sum()) for x in loads] == [2 * 64 * 4] * 4
