"""The Olmo-Hybrid-7B configuration, its counts, its readers and its
adapter on the CPU: published widths against the catalog, ``reduced``
and ``assumed``, the counts against hand counts, the new reader on a
hand-built trace (``None`` where the program has no such scope),
``child.measure`` through the adapter's whole ``check_outputs`` at a
tiny size, the fp8 control, and the benchmark's reference against the
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

CELL = "olmohybrid.spmd.b2s8192"
L, A = "linear_attention", "full_attention"
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
# The catalog's `config` for Olmo-Hybrid-7B (the model-configs guide's
# architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "model_type": "olmo_hybrid", "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
LISTS = ("tokens_per_s", "device_idle_pct.lm", "optimizer_ms_per_step.lm",
         "spmd_dispatch_ms_per_step.lm", "flash_bwd_ms_per_step",
         "setup_compile_s", "gdn_core_ms_per_step", "gdn_core_roofline_pct",
         "gdn_chain_ms_per_step", "gdn_proj_ms_per_step",
         "gdn_chain_roofline_pct")
N_PARAMS = 928_862_196


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    here = {k: cfg["reduced"][k]["here"] for k in REDUCED}
    assert here == {k: cfg[k] for k in REDUCED} == {
        "num_hidden_layers": 4, "layer_types": [L, L, L, A],
        "vocab_size": 12544}
    cut = cfg["reduced"]
    assert cut["num_hidden_layers"]["published"] == 32
    assert cut["vocab_size"]["published"] == 100352
    assert cut["layer_types"]["published"] == [L, L, L, A] * 8
    # the floors: four layers, one whole period; an eighth of the
    # vocabulary exactly; every width as published
    assert cfg["layer_types"] == cut["layer_types"]["published"][:4]
    assert cfg["vocab_size"] * 8 == 100352
    a = cfg["assumed"]
    assert a["stages"] == 8 and "EIGHT pipeline stages" in cfg["stands_for"]
    assert a["vocabulary_slices"] == 8 and a["gdn_chunk"] == 64
    assert a["param_dtype"] == "bfloat16" and a["remat"] \
        and a["loss_chunk"] > 0 and a["optimizer"]["name"] == "adam"
    # every line the row has no key for, each with its source
    for said, source in (("norm_placement", "2501.00656"),
                         ("qk_norm", "QK-norm"),
                         ("no_position_encoding", "rope_theta is null"),
                         ("gated_delta_net", "2412.06464"),
                         ("write_strength", "2411.12537"),
                         ("column_layout", "permutation"),
                         ("gdn_init", "Qwen3-Next"),
                         ("sequence_length", "8192"),
                         ("remat_why", "one checkpoint a layer"),
                         ("loss_chunk_why", "12,544"),
                         ("parameters", "928,862,196"), ("why", "no key")):
        assert source in a[said], said
    assert "928,862,196" in cfg["stands_for"] and cfg["why"]


def test_the_entries_are_found_by_name():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b"]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "chipbench/configs/olmo-hybrid-7b.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b2s8192", "olmo-hybrid-7b")
    assert len(cell["why"]) <= 200
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed == {"step_ms_p90", "peak_hbm_gb", "setup_s", *LISTS}
    (new,) = [m for m in bench["per_layer"]
              if m["name"] == "gdn_chain_roofline_pct"]
    assert new == {"name": "gdn_chain_roofline_pct", "unit": "%",
                   "better": "higher", "source": "device_trace",
                   "layer": "kernels", "moves": "tokens_per_s",
                   "workloads": [CELL]}


def _model():
    from chipbench import child

    _, _, config, traffic = child.find_cell(CELL)
    mod = child.load_file("models", "olmohybrid")
    return mod, mod.Model(config, traffic), traffic


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import gdn_chain_counts, gdn_counts
    from horovod_tpu.models import llama_init

    mod, model, traffic = _model()
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"]) == (2, 8192, 1, "spmd")
    c = model.cfg
    assert (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.vocab_size, c.n_layers, c.conv_taps, c.norm_eps) == (
        3840, 11008, 30, 30, 128, 12544, 4, 4, 1e-6)
    assert (c.linear_key_heads, c.linear_value_heads, c.linear_key_dim,
            c.linear_value_dim, c.linear_beta_max) == (30, 30, 96, 192, 2.0)
    assert c.post_norm == "only" and c.qk_norm is True \
        and not (c.rope_full_attention or c.tie_embeddings or c.attn_gate
                 or c.n_experts or c.partial_rotary)
    assert [(s.stack, s.index, s.mixer, s.dense_ffn, s.rope)
            for s in c.layer_plan()] == [
        ("linear_layers", 0, "linear", True, False),
        ("linear_layers", 1, "linear", True, False),
        ("linear_layers", 2, "linear", True, False),
        ("layers", 0, "attention", True, False)]
    assert model.units_per_step == 16384
    # ISSUE 61's arithmetic, and no input-norm leaf in the tree
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "final_norm", "layers",
                              "linear_layers", "lm_head"]
    assert sorted(shapes["linear_layers"]) == [
        "gdn_a_log", "gdn_ba", "gdn_conv", "gdn_dt_bias", "gdn_in",
        "gdn_out", "gdn_out_norm", "post_attn_norm", "post_mlp_norm",
        "w_down", "w_gate", "w_up"]
    assert sorted(shapes["layers"]) == [
        "k_norm", "post_attn_norm", "post_mlp_norm", "q_norm", "w_down",
        "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    gdn = 3840 * 17280 + 3840 * 60 + 4 * 11520 + 30 + 30 + 192 \
        + 5760 * 3840
    attn = 4 * 3840 * 3840 + 2 * 3840
    ffn = 3 * 3840 * 11008 + 2 * 3840
    assert (gdn, attn, ffn) == (88_750_332, 58_990_080, 126_819_840)
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 3 * (gdn + ffn) + attn + ffn + 2 * 12544 * 3840 + 3840 \
        == N_PARAMS
    assert shapes["linear_layers"]["gdn_in"].shape == (3, 3840, 17280)
    assert shapes["linear_layers"]["gdn_conv"].shape == (3, 4, 11520)
    assert shapes["layers"]["q_norm"].shape == (1, 3840)
    # the counts: the matmul parameters a token passes
    p = mod.matmul_params_per_token(c, 3, 1)
    assert p == 3 * 3840 * (17280 + 60 + 5760) + 4 * 3840 * 3840 \
        + 4 * 3 * 3840 * 11008 + 3840 * 12544 == 880_512_000
    flops, nbytes = model.gated_delta_rule_work()
    assert flops == 3 * 3 * 7 * 96 * 192 * 30 * 16384
    assert model.flops_per_unit() == 6 * p + 12 * 30 * 128 \
        * (8192 * 8193 // 2) / 8192 + flops / 16384
    # q, k at 30 heads of 96, v, o at 30 of 192, two float32 gates a
    # head: forward 2 x (5760 + 5760 + 5760) + 240 B a token, backward
    # that and the gradients of q, k, v and the gates
    assert gdn_counts.rule_bytes(1, 30, 30, 96, 192, 1) \
        == (34560 + 240) + (34560 + 240 + 23040 + 240)
    assert nbytes == 3 * 16384 * 92880
    # the chain: stage one moves [q | k | v] twice forward and three
    # times backward, stage two o, z, y forward and five arrays backward
    assert gdn_chain_counts.chain_bytes(1, 30, 30, 96, 192, 1) \
        == 5 * 11520 * 2 + 60 * 6 + 60 * 8 + 8 * 5760 * 2 == 208_200
    assert model.gdn_chain_work() == 3 * 16384 * 208_200
    floor = gdn_chain_counts.floor_s("TPU v5 lite", model.gdn_chain_work())
    assert round(floor * 1e3, 2) == 12.5
    with pytest.raises(KeyError):
        gdn_chain_counts.floor_s("TPU v9", 1.0)


def _ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_qwen3next_cell.py's hand-built trace: the
    recomputed elementwise fusion under ``hvd.gdn.chain`` (100 ns over
    two steps)."""
    import test_qwen3next_cell as t

    return t._gdn_ctx(monkeypatch, model, rename)


def test_the_chain_roofline_reader_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child

    read = child.load_reader("gdn_chain_roofline_pct").read
    # 20 ns of required bytes at the HBM peak against 50 ns a step
    model = types.SimpleNamespace(gdn_chain_work=lambda: 20e-9 * 819e9)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read(_ctx(monkeypatch, model)) == pytest.approx(100.0 * 20 / 50)
    # a model kind that counts no such work (every accepted kind), a
    # program without the scope: nothing, and no exception
    assert read(_ctx(monkeypatch)) is None
    assert read(_ctx(monkeypatch, model, rename=False)) is None


def test_a_program_from_before_the_scopes_reads_nothing(monkeypatch):
    from chipbench import child, scopes

    model = types.SimpleNamespace(gdn_chain_work=lambda: 96.0)
    read = child.load_reader("gdn_chain_roofline_pct").read
    ctx = _ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None

    def before_the_scopes(ctx, *names, **_):
        raise ValueError(f"no device scopes: {names}")

    monkeypatch.setattr(scopes, "ms_per_step", before_the_scopes)
    assert read(_ctx(monkeypatch, model)) is None


# The cell's shape in small: keys and values of two widths, neither the
# other's, a dense FFN beside the linear mixer, output norms alone.
TINY = {
    "kind": "olmohybrid", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96,
    "rms_norm_eps": 1e-6, "linear_conv_kernel_dim": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_allow_neg_eigval": True, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "rope_parameters": {"rope_theta": None}, "layer_types": [L, L, L, A],
    "assumed": {"remat": "attn/ffn", "param_dtype": "float32",
                "loss_chunk": 64,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
LEAVES = {"embed", "final_norm", "lm_head", "gdn_in", "gdn_ba", "gdn_conv",
          "gdn_a_log", "gdn_dt_bias", "gdn_out_norm", "gdn_out",
          "post_attn_norm", "post_mlp_norm", "q_norm", "k_norm", "wq",
          "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "olmohybrid")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_olmohybrid_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (flash,) = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert flash["shape"] == [2, 128, 4, 16] and flash["kv_heads"] == 4
    assert max(flash["err"].values()) < 6e-3
    (rule,) = [s for s in said
               if s["event"] == "delta_rule_vs_token_by_token"]
    assert rule["shape"] == [2, 128, 2, 24] and rule["key_dim"] == 12
    assert set(rule["err"]) == {"fwd", "dq", "dk", "dv", "dg", "dbeta"}
    assert 1.0 < rule["beta_max"] <= 2.0    # write strengths past 1
    assert max(rule["err"].values()) < 1.2e-2   # bf16 operands, two chunks
    assert rule["required_flops_per_step"] == 3 * 21 * 12 * 24 * 2 * 256
    (chain,) = [s for s in said
                if s["event"] == "chain_vs_float32_expression"]
    assert chain["shape"] == [2, 128, 2 * 24 + 2 * 48]
    assert chain["sizes"] == [2, 2, 12, 24]
    assert set(chain["err"]) == {"q", "k", "v", "y", "dqkvz", "dtaps",
                                 "do", "dz", "dgain"}
    assert max(chain["err"].values()) < 2.5e-2   # bf16 operands
    # the step: every leaf of the tree in both readings, no input norm
    (step,) = [s for s in said if s["event"] == "step_vs_reference"]
    assert (step["tokens"], step["on"]) == (256, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss"} | {"d_" + x for x in LEAVES} \
        | {"moved_" + x for x in LEAVES}
    # The lowering: a fault is reported, not swallowed.
    chunked = "tensor<2x2x2x12x24xf32>"
    kernels = " tpu_custom_call hvd_flash_fwd hvd_gdn_rule_fwd " \
        "hvd_gdn_rule_bwd hvd_gdn_chain_in_fwd hvd_gdn_chain_in_bwd " \
        "hvd_gdn_chain_out_fwd hvd_gdn_chain_out_bwd"
    assert model.check_lowering(chunked, False) is None
    assert model.check_lowering(chunked + kernels, True) is None
    assert "chunk-major" in model.check_lowering("", False)
    assert "scan over tokens" in model.check_lowering(
        chunked + " tensor<128x2x2x24xf32>", False)
    assert "hvd_gdn_chain_in_bwd" in model.check_lowering(
        chunked + kernels.replace("hvd_gdn_chain_in_bwd", ""), True)
    assert "expression" in model.check_lowering(
        chunked + kernels + " tensor<2x128x96xf32>", True)


def test_fp8_in_the_programs_place_is_refused_by_every_comparison():
    child, mod, lane, model = _tiny(control=True)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=11, seconds=0.2,
                      trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert any(s["event"] == "the_reference_in_fp8_in_the_programs_place"
               for s in said)
    assert mod.COMPARISONS == ("flash", "delta rule", "chain", "the step")
    for kind in mod.COMPARISONS:
        assert [f for f in r["faults"] if f.startswith(kind)], kind


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, one layer at a
    time) and the program's (horovod_tpu/models/reference.py) agree on
    logits and loss to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import (
        olmohybrid_forward,
        olmohybrid_loss,
    )

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    p = mod.reference_params(params, c)
    got = jax.jit(lambda p: mod.reference_logits(p, tokens, c))(p)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p: olmohybrid_forward(p, tokens, c))(params)
        ref_loss = olmohybrid_loss(params, batch, c)
    assert float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))) < 2e-5
    loss = jax.jit(lambda p: mod.reference_loss(p, batch, c))(p)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
