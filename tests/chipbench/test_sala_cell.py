"""The MiniCPM-SALA configuration, its counts, its readers and its adapter
on the CPU: published widths against the catalog row key by key,
``reduced`` and ``assumed`` complete, the entries found BY NAME (never by
position: the next cell can be appended), the six readers on a hand-built
trace (``None`` where the program has no such scope), ``sala_counts``
against numbers worked by hand, ``child.measure`` through the adapter's
whole ``check_outputs`` at a tiny size, the fp8 control, and the
benchmark's reference against the program's."""

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

CELL = "minicpmsala.spmd.b1s32768"
NAME = "minicpm-sala"
REDUCED = ["num_hidden_layers", "mixer_types", "vocab_size"]
S4, LA = "minicpm4", "lightning-attn"
MIXERS = [S4] + [LA] * 8 + [S4] + [LA] * 6 + [S4] * 2 + [LA] * 4 + [S4] \
    + [LA] * 6 + [S4] * 3
# The catalog's `config` for MiniCPM-SALA (the model-configs guide's
# architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
NEW_METRICS = ("sparse_select_ms_per_step", "sparse_core_ms_per_step",
               "sparse_core_roofline_pct", "lightning_core_ms_per_step",
               "lightning_core_roofline_pct", "lightning_chain_ms_per_step")
SCOPE_OF = {"sparse_select_ms_per_step": "hvd.sparse.select",
            "sparse_core_ms_per_step": "hvd.sparse.core",
            "lightning_core_ms_per_step": "hvd.lightning.core",
            "lightning_chain_ms_per_step": "hvd.lightning.chain"}
S, L = "sparse_attention", "lightning_attention"


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
        == (32, 4, 4)
    assert cut["mixer_types"]["published"] == MIXERS and len(MIXERS) == 32
    # the floors: one whole period in the published one to three, four
    # layers, an eighth of the vocabulary, every width published
    assert (MIXERS.count(S4), MIXERS.count(LA)) == (8, 24)
    assert cfg["mixer_types"] == MIXERS[:4] == [S4, LA, LA, LA] \
        == cut["mixer_types"]["here"]
    assert (cut["vocab_size"]["published"], cut["vocab_size"]["here"],
            cfg["vocab_size"]) == (73448, 9181, 73448 // 8) \
        and 73448 % 8 == 0
    for key in cut.values():
        assert key.get("kept") or key.get("held")
    a = cfg["assumed"]
    assert a["sparse"] == {**a["sparse"], "block_size": 64, "topk": 64,
                           "kernel_size": 32, "kernel_stride": 16,
                           "init_blocks": 1, "window_size": 2048,
                           "dense_len": 8192}
    assert a["param_dtype"] == "bfloat16" and a["remat"] == "attn" \
        and a["ffn_chunk"] > 0 and a["loss_chunk"] > 0 \
        and a["lightning_chunk"] == 128 \
        and a["optimizer"]["name"] == "adam" \
        and a["optimizer"]["learning_rate"] == 1e-5
    for said in ("why", "lightning_decay", "parameters", "remat_why",
                 "ffn_chunk_why", "loss_chunk_why", "lightning_chunk_why",
                 "compiled_peak_why",
                 "init"):
        assert a[said] and "PLACEHOLDER" not in a[said], said
    assert a["sparse"]["why"] and "LSE" in a["sparse"]["why"]
    assert "1,184.7 M" in a["parameters"] and "1,184.7 M" \
        in cfg["stands_for"] and "8 pipeline stages" in cfg["stands_for"] \
        and "vocabulary parallelism" in cfg["stands_for"] and cfg["why"]
    assert a["compiled_peak_gb"] and a["compiled_peak_gb"] < 15.75 * 1.074
    assert a["compiler_options"]["xla_tpu_scoped_vmem_limit_kib"]


def test_the_entries_fields():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b1s32768", NAME)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "tokens_per_s", "step_ms_p90", "peak_hbm_gb", "setup_s",
        "device_idle_pct.lm", "optimizer_ms_per_step.lm",
        "spmd_dispatch_ms_per_step.lm", "setup_compile_s", *NEW_METRICS}
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


def test_the_adapter_builds_the_stage_through_llamaconfig():
    import jax

    from chipbench import child, sala_counts, ssd_counts

    _, _, config, traffic = child.find_cell(CELL)
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"], traffic["warmup_steps"],
            traffic["calibration_steps"]) == (1, 32768, 1, "spmd", 2, 3)
    assert traffic["traced_steps"] in (3, 5)
    mod = child.load_file("models", "minicpmsala")
    model = mod.Model(config, traffic)
    c = model.cfg
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size,
            c.n_layers, c.d_ff, c.norm_eps, c.rope_theta) == (
        4096, 32, 2, 128, 9181, 4, 16384, 1e-6, 10000)
    assert (c.lightning_heads, c.lightning_head_dim, c.lightning_chunk,
            c.lightning_depth) == (32, 128, 128, 32)
    assert (c.sparse_block, c.sparse_topk, c.sparse_kernel, c.sparse_stride,
            c.sparse_init_blocks, c.sparse_window_blocks,
            c.sparse_dense_len) == (64, 64, 32, 16, 1, 32, 8192)
    assert (c.embed_mult, c.logit_div) == (12, 16.0) \
        and c.residual_mult == pytest.approx(1.4 / 32 ** 0.5)
    assert c.qk_norm == "head" and c.attn_gate and c.loss_chunk \
        and c.ffn_chunk and not (c.tie_embeddings or c.n_experts)
    assert c.layer_types == (S, L, L, L)
    assert not any(s.rope for s in c.layer_plan())
    assert model.units_per_step == 32768
    # ISSUE 55's arithmetic: a lightning layer 285.2 M, the sparse layer
    # 253.8 M, embedding and head 75.2 M; by the leaves 1,184.65 M.
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "final_norm", "lightning_layers",
                              "lm_head", "sparse_layers"]
    n = sum(x.size for x in jax.tree.leaves(shapes))
    ffn = 3 * 4096 * 16384
    lightning = 5 * 4096 * 4096 + ffn + 2 * 4096 + 2 * 128 + 4096
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + ffn + 2 * 4096 + 2 * 128
    assert [round(x / 1e6, 1) for x in (lightning, sparse)] \
        == [285.2, 253.8]
    assert n == 3 * lightning + sparse + 2 * 9181 * 4096 + 4096
    assert round(n / 1e6, 1) == 1184.7
    assert shapes["sparse_layers"]["wk"].shape == (1, 4096, 256)
    assert shapes["lightning_layers"]["wk"].shape == (3, 4096, 4096)
    # the decay: published layers 1-3 of 32
    rates = c.lightning_rates("lightning_layers")
    assert rates.shape == (3, 32)
    assert rates[0, 0] == pytest.approx(
        -2.0 ** (-8 / 32) * (1 - 1 / 31 + 1e-5), rel=1e-6)
    assert rates[2, 31] == pytest.approx(
        -2.0 ** -8 * (1 - 3 / 31 + 1e-5), rel=1e-6)
    # the counts: the matmul parameters a token passes
    p = model.matmul_params_per_token()
    assert p == 3 * 5 * 4096 * 4096 + 4096 * 128 * (3 * 32 + 2 * 2) \
        + 4 * ffn + 4096 * 9181
    assert round(p / 1e6) == 1147
    flops, nbytes = model.sparse_work()
    assert flops == 3 * 4 * 128 * 32 * 124928000
    assert round(flops / 1e12, 2) == 6.14
    # q, o and their gradients six times 32 heads x 128 x 2 B a token, k
    # and v and theirs three times 2 x 2 heads
    assert nbytes == 32768 * (6 * 8192 + 3 * 1024)
    lf, lb = model.lightning_work()
    assert lf == 3 * 3 * 4 * 32 * 128 * 128 * 32768
    # q, k, v, o in bf16 a head, no dt: forward 4 x 8192 B a token,
    # backward 7 x 8192
    assert lb == 3 * 32768 * 11 * 8192 == ssd_counts.core_bytes(
        32768, 32, 128, 128, 32, 3) - 3 * 3 * 32 * 4 * 32768
    assert model.flops_per_unit() == pytest.approx(
        6 * p + (flops + lf) / 32768)
    # bytes bind the recurrence (3.6 ms a layer against 1.0 of FLOPs),
    # FLOPs the sparse core (31 ms against 2 of bytes)
    assert sala_counts.floor_s("TPU v5 lite", lf, lb) == lb / 819e9
    assert round(lb / 819e9 * 1e3 / 3, 1) == 3.6
    assert sala_counts.floor_s("TPU v5 lite", flops, nbytes) \
        == flops / 197e12 and round(flops / 197e12 * 1e3) == 31


def test_the_counts_by_hand_at_a_small_shape():
    """T = 40 in blocks of 8, 3 chosen: tokens of blocks 0, 1, 2 attend
    1, 2, 3 blocks (fewer than 3 have begun for the first 16), tokens of
    blocks 3 and 4 three: each its own block to the causal edge (1 .. 8
    keys: 36 a block) and the others whole."""
    from chipbench import sala_counts

    assert sala_counts.sparse_pairs(40, 8, 3) \
        == 5 * 36 + 8 * 8 * (0 + 1 + 2 + 2 + 2)
    # a last block that is not whole: 4 tokens, 1 .. 4 keys of their own
    assert sala_counts.sparse_pairs(20, 8, 3) \
        == 2 * 36 + 10 + 8 * 8 * 1 + 4 * 8 * 2
    assert sala_counts.sparse_core_flops(2, 40, 4, 16, 8, 3, 5) \
        == 3 * 4 * 16 * 4 * 2 * 628 * 5
    # q and o 4 heads x 16 x 2 B = 128 B a token, k and v together 64:
    # forward q + kv + o, backward q + kv + o + do, then dq + dkv
    assert sala_counts.sparse_core_bytes(2, 40, 4, 1, 16, 5) \
        == (6 * 128 + 3 * 64) * 2 * 40 * 5


def _ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.sparse.select`` (400 ns), the ``while`` and the gather in its
    body under ``hvd.sparse.core`` (400 + 400), the recomputed
    elementwise fusion under ``hvd.lightning.chain`` (100), over two
    steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in rename if isinstance(rename, tuple) else (
                ("hvd.attn.proj", "hvd.sparse.select"),
                ("hvd.moe.dispatch", "hvd.sparse.core"),
                ("hvd.ffn", "hvd.lightning.chain")):
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
    model = types.SimpleNamespace(sparse_work=work, lightning_work=work)
    ctx = _ctx(monkeypatch, model)
    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    assert read["sparse_select_ms_per_step"](ctx) \
        == pytest.approx(400 / 1e6 / 2)
    assert read["sparse_core_ms_per_step"](ctx) \
        == pytest.approx(800 / 1e6 / 2)
    assert read["lightning_chain_ms_per_step"](ctx) \
        == pytest.approx(100 / 1e6 / 2)
    assert read["lightning_core_ms_per_step"](ctx) is None
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["sparse_core_roofline_pct"](_ctx(monkeypatch, model)) \
        == pytest.approx(100.0 * 96 / 400)
    assert read["lightning_core_roofline_pct"](
        _ctx(monkeypatch, model)) is None
    ctx = _ctx(monkeypatch, model, rename=(
        ("hvd.moe.dispatch", "hvd.lightning.core"),))
    assert read["lightning_core_ms_per_step"](ctx) \
        == pytest.approx(800 / 1e6 / 2)
    assert read["lightning_core_roofline_pct"](_ctx(
        monkeypatch, model, rename=(
            ("hvd.moe.dispatch", "hvd.lightning.core"),))) \
        == pytest.approx(100.0 * 96 / 400)
    # a model kind that counts no such work: nothing, and no exception
    assert read["sparse_core_roofline_pct"](_ctx(monkeypatch)) is None


def test_each_new_scope_is_named_by_one_reader():
    """``tests/chipbench/test_scope_metrics.py`` holds the table minus
    what some reader names to a fixed set: each of the four new scopes is
    named by exactly one of the new readers' calls; the two shares call
    none of their own."""
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

    work = lambda: (2, 96.0)                                 # noqa: E731
    model = types.SimpleNamespace(sparse_work=work, lightning_work=work)
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
    "kind": "minicpmsala", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 4, "mixer_types": [S4, LA, LA, LA],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "hidden_act": "silu",
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 4,
    "attention_bias": False, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
    "reduced": {"num_hidden_layers": {"published": 32, "here": 4}},
    "assumed": {"remat": "attn/ffn", "param_dtype": "float32",
                "loss_chunk": 64, "ffn_chunk": 64, "lightning_chunk": 32,
                "sparse": {"block_size": 16, "topk": 4, "kernel_size": 8,
                           "kernel_stride": 4, "init_blocks": 1,
                           "window_size": 32, "dense_len": 64},
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 1, "seq": 192,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
LEAVES = {"embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv",
          "wo", "wg", "q_norm", "k_norm", "out_norm", "mlp_norm", "w_gate",
          "w_up", "w_down"}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "minicpmsala")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    assert model.cfg.layer_types == (S, L, L, L)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (sel,) = [s for s in said if s["event"] == "selection_vs_reference"]
    assert sel["shape"] == [1, 192, 4, 16] and sel["kv_heads"] == 2
    # bf16 operands on both sides; the program rounds its pooled keys
    assert sel["sets_equal_share"] > 0.9
    assert sel["worst_margin_of_a_swapped_block"] < mod.SELECT_MARGIN_TOL
    assert 3 < sel["blocks_a_token_mean"] < 4
    assert sel["visited_over_chosen"] >= 1.0
    # block 0, the last two begun: 1, 2, then 3 of at most 4
    assert 0.75 < sel["forced_share"] < 0.85
    (core,) = [s for s in said
               if s["event"] == "sparse_core_vs_explicit_mask"]
    assert set(core["err"]) == {"fwd", "dq", "dk", "dv"}
    assert max(core["err"].values()) < 1e-2
    assert core["required_flops_per_step"] == 3 * 4 * 16 * 4 * (
        12 * 136 + 16 * 16 * (0 + 1 + 2 + 3 * 9))
    (rec,) = [s for s in said if s["event"] == "lightning_vs_token_by_token"]
    assert rec["shape"] == [1, 192, 4, 16] and rec["rates_of_layer"] == 1
    assert max(rec["err"].values()) < 1e-2
    assert rec["required_flops_per_step"] == 3 * 3 * 4 * 4 * 16 * 16 * 192
    (step,) = [s for s in said if s["event"] == "step_vs_reference"]
    assert (step["tokens"], step["on"]) == (192, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss"} | {
        k + x for x in LEAVES for k in ("d_", "moved_")}
    # the selection on the step's own activations, held to the
    # reference's five steps there: in float32 the same sets
    (chose,) = step["selection_of_the_step"]
    assert chose["sets_equal_share"] == 1.0
    assert chose["worst_margin_of_a_swapped_block"] == 0.0
    assert 3 < chose["blocks_a_token_mean"] < 4
    # The lowering: a fault is reported, not swallowed.
    kernels = " tpu_custom_call hvd_sparse_attn_fwd hvd_sparse_attn_bwd " \
        "hvd_ssd_fwd hvd_ssd_bwd "
    assert model.check_lowering("tensor<4x192x192xf32>", False) is None
    assert model.check_lowering(kernels + "tensor<1x192x64xf32>",
                                True) is None
    assert "materialised" in model.check_lowering(
        kernels + "tensor<4x192x192xf32>", True)
    for name in kernels.split():
        assert name in model.check_lowering(kernels.replace(name, ""), True)


def test_a_selection_that_drops_a_block_is_refused(monkeypatch):
    """The program's selection with each token's best free block left
    out (the next best in its place): the sets differ across a margin no
    rounding explains, on the operands of (a) and on the step's own
    activations in (d)."""
    import jax
    import jax.numpy as jnp

    child, mod, lane, model = _tiny()

    def drops_the_best(sets, score):
        free = sets & ~(jnp.arange(12) > jnp.arange(192)[:, None, None]
                        // 16 - 2) & (jnp.arange(12) > 0)
        best = jnp.argmax(jnp.where(free, score, -jnp.inf), -1)
        rest = ~sets & (jnp.arange(12) <= jnp.arange(192)[:, None, None]
                        // 16)
        other = jnp.argmin(jnp.where(rest, score, jnp.inf), -1)
        swap = jnp.any(free, -1) & jnp.any(rest, -1)
        hot = lambda i: jnp.arange(12) == i[..., None]       # noqa: E731
        return jnp.where(swap[..., None],
                         (sets & ~hot(best)) | hot(other), sets)

    monkeypatch.setattr(model, "_select", lambda q, k: drops_the_best(
        *mod._reference_select(model.cfg)(q, k)))
    monkeypatch.setattr(mod, "_program_table", lambda c: jax.jit(
        lambda lp, x: drops_the_best(*mod.reference_layer_selection(
            jax.tree.map(lambda w: w.astype(jnp.float32), lp),
            x.astype(jnp.float32), c))))
    r = child.measure(model, lane, TINY_TRAFFIC, seed=5, seconds=0.2,
                      trace=False, t0=time.time(), say=lambda **k: None)
    for where in ("selection", "the step's selection"):
        assert any(f.startswith(where + ":") and "margin" in f
                   for f in r["faults"]), where
        assert any(f.startswith(where + ":") and "sets" in f
                   for f in r["faults"]), where


def test_fp8_in_the_programs_place_is_refused_by_every_comparison():
    """At this size (twelve blocks, one of a token's four free) fp8's
    selection differs from the reference's in a few sets by margins the
    chip's bound, set at 512 blocks, lets pass: it is held to being
    WORSE than the program's, which agrees to the last set here; the
    other three comparisons refuse it as on the chip."""
    child, mod, lane, model = _tiny(control=True)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=11, seconds=0.2,
                      trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert any(s["event"] == "the_reference_in_fp8_in_the_programs_place"
               for s in said)
    # (of the step's three limits the gradients' refuses it here; the
    # loss's and the change's are set at published widths: the chip's
    # control holds fp8 to each, ``STEP_LIMITS``)
    for kind in mod.COMPARISONS[1:] + mod.STEP_LIMITS[1:2]:
        assert [f for f in r["faults"] if f.startswith(kind)], kind
    # the selection of the step is the program's own in the control too
    assert not [f for f in r["faults"] if "the step's selection" in f]
    (sel,) = [s for s in said if s["event"] == "selection_vs_reference"]
    assert sel["sets_equal_share"] < 1.0
    assert sel["worst_margin_of_a_swapped_block"] > 0.01


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, a layer at a
    time, the gradients chained by hand, the sparse layer given the
    program's sets) and the program's
    (horovod_tpu/models/reference.py) agree on the loss and every
    gradient leaf to float32 rounding, and on the sets."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import sala_forward, sala_loss

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 192), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    seen = {}
    loss, verdicts = mod.reference_loss_and_grads(
        params, batch, c, lambda where, g: seen.setdefault(
            where, {}).update(g))
    sets, _ = jax.jit(
        lambda lp, x: mod.reference_layer_selection(lp, x, c))(
        {name: w[0] for name, w in params["sparse_layers"].items()},
        c.embed_mult * params["embed"][tokens])
    with jax.default_matmul_precision("highest"):
        want, grads = jax.jit(jax.value_and_grad(
            lambda p: sala_loss(p, batch, c)))(params)
        theirs = []
        sala_forward(params, tokens, c, theirs)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    assert bool(jnp.all(sets == theirs[0]))
    # ... and the program's table, in float32 here, is those sets
    assert [v[:2] for v in verdicts] == [(1.0, 0.0)]
    for where, got in seen.items():
        for name, g in got.items():
            w = grads[where[0]][name][where[1]] if where else grads[name]
            err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            assert err < 1e-4, (where, name, err)
    assert sum(len(g) for g in seen.values()) == 3 + 12 + 3 * 13
