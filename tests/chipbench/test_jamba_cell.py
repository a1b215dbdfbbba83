"""The AI21-Jamba2-3B configuration, its counts, its readers and its
adapter on the CPU: published widths against the catalog, ``reduced``,
the counts against hand counts, the four ``ssm_*`` readers on a
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

CELL = "jamba2.spmd.b1s8192"
REDUCED = ["num_hidden_layers"]
# The catalog's `config` for AI21-Jamba2-3B (the model-configs guide's
# architectures.jsonl), less the reduced key.
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
NEW_METRICS = ("ssm_core_ms_per_step", "ssm_core_roofline_pct",
               "ssm_chain_ms_per_step", "ssm_proj_ms_per_step")
M, A = "mamba", "full_attention"


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "jamba2-3b.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones_and_the_cut_is_written_down():
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert list(cfg["reduced"]) == REDUCED
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"], cfg["num_hidden_layers"]) \
        == (28, 14, 14)
    # the floors: one whole period of attn_layer_period, every width
    # published, the whole vocabulary
    assert cfg["num_hidden_layers"] % cfg["attn_layer_period"] == 0
    assert "0-13" in cut["kept"]
    a = cfg["assumed"]
    assert a["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert a["param_dtype"] == "bfloat16" and a["remat"] \
        and a["loss_chunk"] > 0 and a["optimizer"]["name"] == "adam"
    for said in ("why", "head_dim_why", "layer_types", "parameters",
                 "remat_why", "loss_chunk_why", "scan_why", "init"):
        assert a[said] and "PLACEHOLDER" not in a[said], said
    assert "1,598.6 M" in a["parameters"] and "1,598.6 M" \
        in cfg["stands_for"] and cfg["why"]
    assert a["compiler_options"]["xla_tpu_scoped_vmem_limit_kib"]


def test_the_entries_fields():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == "jamba2-3b"]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "chipbench/configs/jamba2-3b.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b1s8192", "jamba2-3b")
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
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))
    # a quarter of the cells, rounded down, may take four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(len(bench["workloads"]) // 4, 1)


def test_the_adapter_builds_the_stage_through_llamaconfig():
    import jax

    from chipbench import child, ssm_counts
    from horovod_tpu.models import llama_init

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"], traffic["warmup_steps"],
            traffic["calibration_steps"], traffic["traced_steps"]) \
        == (1, 8192, 1, "spmd", 2, 3, 5)
    mod = child.load_file("models", "jamba")
    model = mod.Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.vocab_size, c.n_layers, c.conv_taps, c.norm_eps) == (
        2560, 8192, 20, 1, 128, 65536, 14, 4, 1e-6)
    assert (c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank,
            c.mamba_conv_bias) == (5120, 16, 160, True)
    assert c.tie_embeddings and c.n_experts == 0 and c.loss_chunk \
        and not (c.qk_norm or c.attn_gate or c.rope_full_attention)
    assert [(s.stack, s.index, s.rope) for s in c.layer_plan()] == [
        ("mamba_layers", i, False) for i in range(7)] + [
        ("layers", 0, False)] + [
        ("mamba_1_layers", i, False) for i in range(6)]
    assert model.units_per_step == 8192
    # ISSUE 47's arithmetic: a Mamba mixer 41.24 M, the SwiGLU 62.91 M,
    # an attention mixer 13.76 M, the embedding 167.8 M: 1,598.6 M.
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "final_norm", "layers",
                              "mamba_1_layers", "mamba_layers"]
    n = sum(x.size for x in jax.tree.leaves(shapes))
    mamba = 2560 * 10240 + 5 * 5120 + 5120 * 192 + 192 + 160 * 5120 \
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    ffn = 3 * 2560 * 8192
    assert round(mamba / 1e6, 2) == 41.24 and round(ffn / 1e6, 2) == 62.91
    assert n == 13 * mamba + attn + 14 * (ffn + 2 * 2560) \
        + 65536 * 2560 + 2560
    assert round(n / 1e6, 1) == 1598.6
    assert shapes["mamba_layers"]["ssm_in"].shape == (7, 2560, 10240)
    assert shapes["mamba_1_layers"]["ssm_a_log"].shape == (6, 5120, 16)
    assert shapes["layers"]["wk"].shape == (1, 2560, 128)
    # the counts: the matmul parameters a token passes
    p = mod.matmul_params_per_token(c, 13, 1)
    assert p == 13 * (2560 * 10240 + 5120 * 192 + 160 * 5120
                      + 5120 * 2560) + 2560 * 128 * 42 \
        + 14 * 3 * 2560 * 8192 + 2560 * 65536
    flops, nbytes = model.selective_scan_work()
    assert flops == 13 * 3 * 7 * 5120 * 16 * 8192
    assert model.flops_per_unit() == 6 * p + 12 * 20 * 128 \
        * (8192 * 8193 // 2) / 8192 + flops / 8192
    # u, y in bf16, dt in float32, B and C in bf16: forward 41,024 B a
    # token, backward 71,808
    assert ssm_counts.scan_bytes(1, 5120, 16, 1) == 41024 + 71808
    assert nbytes == 13 * 8192 * (41024 + 71808)
    # bytes bind: 1.13 ms a layer against 0.07 ms of FLOPs
    floor = ssm_counts.floor_s("TPU v5 lite", flops, nbytes)
    assert floor == nbytes / 819e9 and round(floor * 1e3 / 13, 2) == 1.13
    assert round(flops / 197e12 * 1e3 / 13, 2) == 0.07


def _ssm_ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.ssm.proj`` (400 ns), the ``while`` and the gather in its body
    under ``hvd.ssm.core`` (400 + 400), the recomputed elementwise
    fusion under ``hvd.ssm.chain`` (100), over two steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in (("hvd.attn.proj", "hvd.ssm.proj"),
                         ("hvd.moe.dispatch", "hvd.ssm.core"),
                         ("hvd.ffn", "hvd.ssm.chain")):
            text = text.replace(old, new)
    ctx = t._ctx(monkeypatch, [("jit_hvd_grad", text),
                               ("jit_hvd_apply", t.APPLY)])
    ctx.model = model if model is not None else types.SimpleNamespace()
    return ctx


def test_the_ssm_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child

    # 96 ns of required work at the HBM peak, 2 FLOPs
    model = types.SimpleNamespace(
        selective_scan_work=lambda: (2, 96e-9 * 819e9))
    ctx = _ssm_ctx(monkeypatch, model)
    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    assert read["ssm_proj_ms_per_step"](ctx) == pytest.approx(400 / 1e6 / 2)
    assert read["ssm_core_ms_per_step"](ctx) == pytest.approx(800 / 1e6 / 2)
    assert read["ssm_chain_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["ssm_core_roofline_pct"](ctx) == pytest.approx(
        100.0 * 96 / 400)
    # a model kind that counts no such work: nothing, and no exception
    assert read["ssm_core_roofline_pct"](_ssm_ctx(monkeypatch)) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_scope_reads_nothing(monkeypatch, metric):
    """A model with no such layer (the scopes of another), a program
    with no scope tables at all, and a program from before the scopes
    (its table does not know the name, as the parent commit's does
    not): None, never 0, no exception."""
    from chipbench import child, scopes

    model = types.SimpleNamespace(selective_scan_work=lambda: (2, 96.0))
    read = child.load_reader(metric).read
    assert read(_ssm_ctx(monkeypatch, model, rename=False)) is None
    ctx = _ssm_ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None

    def before_the_scopes(ctx, *names, **_):
        raise ValueError(f"no device scopes: {names}")

    monkeypatch.setattr(scopes, "ms_per_step", before_the_scopes)
    assert read(_ssm_ctx(monkeypatch, model)) is None


TINY = {
    "kind": "jamba", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 96,
    "rms_norm_eps": 1e-6, "attn_layer_period": 4, "attn_layer_offset": 2,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_experts": 1, "num_experts_per_tok": 1, "sliding_window": None,
    "tie_word_embeddings": True,
    "assumed": {"remat": "attn/ffn", "param_dtype": "float32",
                "head_dim": 16, "loss_chunk": 64,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 1, "seq": 160,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
LEAVES = {"embed", "final_norm", "ssm_norm", "ssm_in", "ssm_conv",
          "ssm_conv_bias", "ssm_x", "ssm_dt_norm", "ssm_b_norm",
          "ssm_c_norm", "ssm_dt", "ssm_dt_bias", "ssm_a_log", "ssm_d",
          "ssm_out", "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
          "w_gate", "w_up", "w_down"}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "jamba")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_jamba_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    assert model.cfg.layer_types == (M, M, A, M)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (flash,) = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert flash["shape"] == [1, 160, 4, 16] and flash["kv_heads"] == 1
    assert max(flash["err"].values()) < 6e-3
    (scan,) = [s for s in said
               if s["event"] == "selective_scan_vs_token_by_token"]
    assert scan["shape"] == [1, 160, 128] and scan["states"] == 16
    assert set(scan["err"]) == {"fwd", "du", "ddt", "dA", "dB", "dC", "dD"}
    # bf16 operands, y and du rounded to bf16 as they leave
    assert max(scan["err"].values()) < 1e-2
    assert scan["required_flops_per_step"] == 3 * 3 * 7 * 128 * 16 * 160
    # the step: every leaf of the tree in both readings
    (step,) = [s for s in said if s["event"] == "step_vs_reference"]
    assert (step["tokens"], step["on"]) == (160, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss"} | {"d_" + x for x in LEAVES} \
        | {"moved_" + x for x in LEAVES}
    # The lowering: a fault is reported, not swallowed.
    kernels = " tpu_custom_call hvd_flash_fwd hvd_ssm_scan_fwd " \
        "hvd_ssm_scan_bwd "
    assert model.check_lowering("tensor<1x160x128xf32>", False) is None
    assert model.check_lowering(kernels, True) is None
    assert "materialised" in model.check_lowering(
        "tensor<160x1x128x16xf32>", False)
    assert "hvd_ssm_scan_bwd" in model.check_lowering(
        kernels.replace("hvd_ssm_scan_bwd", ""), True)


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
    scan = [f for f in r["faults"] if f.startswith("selective scan")]
    assert len(scan) >= 5, scan           # out and the gradients


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, one layer at a
    time) and the program's (horovod_tpu/models/reference.py) agree on
    logits and loss to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import jamba_forward, jamba_loss

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    p = mod.reference_params(params, c)
    got = jax.jit(lambda p: mod.reference_logits(p, tokens, c))(p)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p: jamba_forward(p, tokens, c))(params)
        ref_loss = jax.jit(lambda p: jamba_loss(p, batch, c))(params)
    assert float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))) < 2e-5
    loss = jax.jit(lambda p: mod.reference_loss(p, batch, c))(p)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
