"""The OLMoE configuration, its counts, its readers and its adapter on
the CPU: published widths, the arithmetic of ``moe_counts.py``, the
grouped-GEMM readers on a hand-built trace, and ``child.measure``
through the adapter's whole ``check_outputs`` at a tiny size."""

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


def test_widths_are_the_published_ones():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_experts", "num_experts_per_tok",
        "vocab_size", "rope_theta", "norm_topk_prob", "rms_norm_eps",
        "max_position_embeddings", "tie_word_embeddings")} == {
        "hidden_size": 2048, "intermediate_size": 1024,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "vocab_size": 50304,
        "rope_theta": 10000, "norm_topk_prob": False,
        "rms_norm_eps": 1e-5, "max_position_embeddings": 4096,
        "tie_word_embeddings": False}
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["reduced"]["num_hidden_layers"] == {
        "published": 16, "here": cfg["num_hidden_layers"]}
    assert cfg["stands_for"] and cfg["assumed"]["remat"]
    assert cfg["assumed"]["router_aux_loss_coef"] == 0.01


def test_the_adapter_builds_the_published_model_through_llamaconfig():
    from chipbench import child

    _, _, config, traffic = child.find_cell("olmoe1b7b.spmd.b2s4096")
    model = child.load_file("models", "olmoe").Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.n_experts, c.n_experts_per_token, c.vocab_size) == (
        2048, 1024, 16, 16, 128, 64, 8, 50304)
    assert c.qk_norm and not c.norm_topk_prob and c.moe_impl == "grouped"
    assert model.units_per_step == 8192
    # One layer: experts 402.7 M, attention 16.8 M, router 0.13 M.
    import jax

    from horovod_tpu.models import llama_init

    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    layer = 3 * 64 * 2048 * 1024 + 4 * 2048 * 2048 + 2048 * 64 + 4 * 2048
    assert n == c.n_layers * layer + 2 * 50304 * 2048 + 2048


@pytest.mark.parametrize("layers", [2, 3])   # the cell's depth; the trial's
def test_required_work_is_counted_from_shapes(layers):
    from chipbench import moe_counts as mc

    n = mc.moe_matmul_params(2048, 1024, 16, 16, 128, layers, 50304, 64, 8)
    assert n == layers * (4 * 2048 * 2048 + 2048 * 64
                          + 8 * 3 * 2048 * 1024) + 2048 * 50304
    assert mc.moe_train_flops_per_token(
        2048, 1024, 16, 16, 128, layers, 50304, 64, 8, 4096) \
        == 6 * n + 6 * layers * 4096 * 2048
    flops = mc.grouped_gemm_flops_per_step(8192, 8, 2048, 1024, layers)
    assert flops == layers * 9 * 2 * 65536 * 2048 * 1024
    nbytes = mc.grouped_gemm_bytes_per_step(8192, 8, 2048, 1024, layers,
                                            64)
    assert nbytes == layers * 3 * 3 * 2 * (65536 * 2048 + 65536 * 1024
                                           + 64 * 2048 * 1024)
    floor, binds = mc.grouped_gemm_floor_s("TPU v5 lite", flops, nbytes)
    assert binds == "compute" and floor == pytest.approx(flops / 197e12)
    if layers == 2:   # what the readers' docstrings and PERF.md quote
        assert round(floor * 1e3, 1) == 25.1
        assert round(nbytes / 819e9 * 1e3, 1) == 14.7
    with pytest.raises(KeyError):
        mc.grouped_gemm_floor_s("cpu", flops, nbytes)


# Two whole steps [1000, 3000]; in each a flash kernel (named by its
# kernel_metadata), a grouped GEMM (a Mosaic call without one), a sort,
# a row gather (a kCustom fusion, as the v5e's compiler emits it) and an
# elementwise fusion.
_CALL = ('%tpu_custom_call.{n} = bf16[64,128]{{1,0:T(8,128)(2,1)}} '
         'custom-call(bf16[64,128]{{1,0:T(8,128)(2,1)}} %p.{n}), '
         'custom_call_target=\\"tpu_custom_call\\"{extra}')
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
    events { metadata_id: 2 offset_ps: 1100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 1400000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 1450000 duration_ps: 70000 }
    events { metadata_id: 5 offset_ps: 1520000 duration_ps: 200000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 2400000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 2450000 duration_ps: 70000 }
    events { metadata_id: 5 offset_ps: 2520000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "FLASH" } }
  event_metadata { key: 2 value { id: 2 name: "GMM" } }
  event_metadata { key: 3 value { id: 3 name: "%sort.3 = (s32[1024]{0:T(1024)}, s32[1024]{0:T(1024)}) sort(s32[1024]{0:T(1024)} %a, s32[1024]{0:T(1024)} %b), dimensions={0}, is_stable=true, to_apply=%lt" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[1024,64]{1,0:T(8,128)(2,1)} fusion(bf16[128,64]{1,0:T(8,128)(2,1)} %h, s32[1024]{0:T(1024)S(1)} %i), kind=kCustom, calls=%fused_computation.4" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]{1,0:T(8,128)(2,1)} %p.1), kind=kLoop, calls=%fused_computation.5" } }
  event_metadata { key: 10 value { id: 10 name: "jit_hvd_grad(1)" } }
}
""".replace("FLASH", _CALL.format(
    n=1, extra=', frontend_attributes={kernel_metadata={\\"kernel\\":'
               '\\"hvd_flash_fwd\\"}}')).replace(
    "GMM", _CALL.format(n=2, extra=""))


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData

    from chipbench import xplane

    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    (chip,) = xplane.chips(profile)
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)
    return chip


def test_grouped_gemm_readers_tell_the_kernels_from_flash(chip,
                                                         monkeypatch):
    import jax

    from chipbench import child

    model = types.SimpleNamespace(
        grouped_gemm_work=lambda: (197e12 * 150e-9, 1.0))
    ctx = types.SimpleNamespace(chip=chip, model=model)
    assert child.load_reader("flash_ms_per_step").read(ctx) \
        == pytest.approx(400 / 1e6)          # every Mosaic call
    assert child.load_reader("moe_gmm_ms_per_step").read(ctx) \
        == pytest.approx(300 / 1e6)          # not the flash kernel
    assert child.load_reader("moe_dispatch_ms_per_step").read(ctx) \
        == pytest.approx(120 / 1e6)          # sort + gather, not kLoop
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    # 150 ns of required work at peak over 300 ns of kernel time
    assert child.load_reader("moe_gmm_roofline_pct").read(ctx) \
        == pytest.approx(50.0)
    # a program or a model kind without them: nothing, not zero
    dense = types.SimpleNamespace(chip=chip, model=types.SimpleNamespace())
    assert child.load_reader("moe_gmm_roofline_pct").read(dense) is None


TINY_OLMOE = {"kind": "olmoe", "vocab_size": 128, "hidden_size": 64,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 4, "intermediate_size": 32,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
              "num_experts": 8, "num_experts_per_tok": 3,
              "norm_topk_prob": False,
              "assumed": {"remat": "attn+moe", "param_dtype": "float32",
                          "router_aux_loss_coef": 0.01,
                          "optimizer": {"name": "adam",
                                        "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}


def test_measure_with_a_tiny_olmoe_adapter_checks_every_comparison():
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    olmoe = child.load_file("models", "olmoe")
    model = olmoe.Model(TINY_OLMOE, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 5,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    events = {s["event"]: s for s in said}
    assert {"flash_vs_blockwise", "expert_load",
            "program_vs_reference"} <= set(events)
    assert [s["which"] for s in said
            if s["event"] == "grouped_mm_vs_numpy"] == ["gate_up", "down"]
    load = events["expert_load"]
    assert load["routed_slots_per_layer"] == [2 * 128 * 3] * 2
    assert all(m >= 1.0 for m in load["max_over_mean_per_layer"])
    err = events["program_vs_reference"]["err"]
    assert max(err.values()) < 1e-4, err
    assert set(err) == {"logits", "logits_decided_tokens", "loss",
                        "d_moe_gate", "d_moe_up", "d_moe_down", "d_router",
                        "d_q_norm", "d_k_norm"}
    # A fault is reported, not swallowed: a bound nothing can meet.
    assert model.check_lowering("tpu_custom_call hvd_flash_fwd @gmm @tgmm",
                                True) is None
    assert "@tgmm" in model.check_lowering(
        "tpu_custom_call hvd_flash_fwd @gmm_1", True)
    olmoe.LOGITS_TOL = 0.0
    faults = model._check_against_reference(
        lane.params_of(lane.build(model, child.key_of(1), {})[1]),
        child.key_of(2), lambda **k: None)
    assert any("logits error" in f for f in faults)


def test_the_benchmarks_reference_is_the_programs_reference():
    """Two copies by design (the benchmark's may not move with the
    program); on the same weights they give the same numbers."""
    import jax
    import numpy as np

    from chipbench import child
    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import olmoe_loss

    olmoe = child.load_file("models", "olmoe")
    model = olmoe.Model(TINY_OLMOE, TINY_TRAFFIC)
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(3))
    batch = olmoe._batch(jax.random.PRNGKey(4), 2, 16, c.vocab_size)
    np.testing.assert_allclose(
        olmoe.reference_loss(olmoe.reference_params(params), batch, c,
                             c.moe_aux_weight),
        olmoe_loss(params, batch, c), rtol=1e-6)
