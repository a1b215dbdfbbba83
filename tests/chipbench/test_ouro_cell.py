"""The Ouro-2.6B configuration, its counts, its readers and its adapter
on the CPU: published widths against the catalog, ``reduced`` and
``assumed``, the parameter count against ``llama_init``'s, the counts
against hand counts, the three readers on a hand-built trace (``None``
where the program has no such scope), the benchmark's reference and its
visit-by-visit gradients against the program's reference, and
``child.measure`` through the adapter's whole ``check_outputs`` at a tiny
size with the fp8 control. Entries are found by NAME, never by position
or as an exact set: the next cell and the next metric can be appended."""

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

CELL = "ouro.spmd.b2s4096"
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
# The catalog's `config` for Ouro-2.6B (the model-configs guide's
# architectures.jsonl), less the reduced keys.
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False}
LISTS = ("tokens_per_s", "device_idle_pct.lm", "optimizer_ms_per_step.lm",
         "spmd_dispatch_ms_per_step.lm", "flash_bwd_ms_per_step",
         "setup_compile_s")
NEW_METRICS = {"exit_heads_ms_per_step": ("ms", "lower"),
               "exit_heads_roofline_pct": ("%", "higher"),
               "loop_ms_per_step": ("ms", "lower")}
N_PARAMS = 666_996_737
LAYER = 51_388_416


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro-2.6b.json")) as f:
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
        "num_hidden_layers": 12, "layer_types": ["full_attention"] * 12,
        "vocab_size": 12288}
    cut = cfg["reduced"]
    assert cut["num_hidden_layers"]["published"] == 48
    assert cut["vocab_size"]["published"] == 49152 == 4 * cfg["vocab_size"]
    assert cut["layer_types"]["published"] == ["full_attention"] * 48
    a = cfg["assumed"]
    assert a["stages"] == 4 == a["vocabulary_slices"] \
        and "FOUR-stage pipeline" in cfg["stands_for"]
    assert a["param_dtype"] == "bfloat16" and a["remat"] \
        and a["loss_chunk"] > 0 and a["optimizer"]["name"] == "adam" \
        and a["exit_entropy_weight"] == 0.1
    # every line the row has no key for, each with its source and the
    # sentence that lets the published form win
    wins = "the published form wins"
    for said, source in (("no_bias", wins), ("four_norms", wins),
                         ("norm_inside_the_loop", wins), ("rope", wins),
                         ("exit_gate", wins), ("exit_gate_init", wins),
                         ("objective", wins),
                         ("exit_entropy_weight_why", wins),
                         ("sequence_length", "4096"),
                         ("remat_why", "GB"), ("loss_chunk_why", "12,288"),
                         ("parameters", "666,996,737"),
                         ("why", "2510.25741")):
        assert source in a[said], said
    assert "Linear(2048, 1) WITH a bias" in a["exit_gate"]
    assert "666,996,737" in cfg["stands_for"] and cfg["why"]


def test_the_entries_are_found_by_name():
    cfg, bench = _config(), _bench()
    (entry,) = [c for c in bench["configs"] if c["name"] == "ouro-2.6b"]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "chipbench/configs/ouro-2.6b.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "spmd.b2s4096", "ouro-2.6b")
    assert len(cell["why"]) <= 200
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in bench[s] if CELL in m.get("workloads", [CELL])}
    assert listed >= {"step_ms_p90", "peak_hbm_gb", "setup_s", *LISTS,
                      *NEW_METRICS}
    for name, (unit, better) in NEW_METRICS.items():
        (new,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert new == {"name": name, "unit": unit, "better": better,
                       "source": "device_trace", "layer": "model",
                       "moves": "tokens_per_s", "workloads": [CELL]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def _model():
    from chipbench import child

    _, _, config, traffic = child.find_cell(CELL)
    mod = child.load_file("models", "ouro")
    return mod, mod.Model(config, traffic), traffic


def test_the_adapter_builds_the_share_through_llamaconfig():
    import jax

    from chipbench import ouro_counts
    from horovod_tpu.models import llama_init

    mod, model, traffic = _model()
    assert (traffic["batch"], traffic["seq"], traffic["ranks"],
            traffic["lane"]) == (2, 4096, 1, "spmd")
    c = model.cfg
    assert (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.vocab_size, c.n_layers, c.norm_eps, c.rope_theta) == (
        2048, 5632, 16, 16, 128, 12288, 12, 1e-6, 1000000)
    assert (c.loop_steps, c.exit_entropy_weight, c.post_norm) == (
        4, 0.1, True)
    assert not (c.tie_embeddings or c.qk_norm or c.attn_gate
                or c.n_experts or c.layer_types or c.sliding_window)
    assert {s.kind for s in c.layer_plan()} == {
        ("attention", True, 0, True)} and len(c.layer_plan()) == 12
    assert model.units_per_step == 8192
    # ISSUE 64's arithmetic
    shapes = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == ["embed", "exit_gate_b", "exit_gate_w",
                              "final_norm", "layers", "lm_head"]
    assert sorted(shapes["layers"]) == [
        "attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm",
        "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    assert 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 == LAYER
    assert sum(x.size for x in jax.tree.leaves(shapes["layers"])) \
        == 12 * LAYER
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 12 * LAYER + 2 * 12288 * 2048 + 2048 + 2049 == N_PARAMS
    assert 48 * LAYER + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657
    # the counts, a weight once a USE
    uses = ouro_counts.matmul_param_uses_per_token(
        2048, 5632, 16, 16, 128, 12, 12288, 4)
    assert uses == 4 * 12 * 51_380_224 + 4 * 12288 * 2048 + 4 * 2048 \
        == 2_566_922_240
    assert model.flops_per_unit() == 6 * uses \
        + 48 * 12 * 16 * 128 * (4096 * 4097 // 2) / 4096
    assert round(model.flops_per_unit() / 1e9, 2) == 17.82
    assert model.exit_heads_work() == 6 * 4 * 8192 * 2048 * 12288
    floor = ouro_counts.floor_s("TPU v5 lite", model.exit_heads_work())
    assert round(floor * 1e3, 2) == 25.12
    with pytest.raises(KeyError):
        ouro_counts.floor_s("TPU v9", 1.0)
    # the lowering: a fault is reported, not swallowed
    exits = "tensor<4x2x4096x2048xbf16>"
    assert model.check_lowering(exits, False) is None
    assert model.check_lowering(
        exits + " tpu_custom_call hvd_flash_fwd", True) is None
    assert "exits of 4 trips" in model.check_lowering("", False)
    assert "hvd_flash_fwd" in model.check_lowering(
        exits + " tpu_custom_call", True)


def _ctx(monkeypatch, model=None, rename=True):
    """tests/chipbench/test_scope_metrics.py's hand-built chip and
    program text with the scopes renamed: the projection's fusion under
    ``hvd.head`` (400 ns), the ``while`` and the gather in its body
    under ``hvd.exit`` (400 + 400), the recomputed elementwise fusion
    under ``hvd.loop`` (100), over two steps."""
    import test_scope_metrics as t

    text = t._grad_text()
    if rename:
        for old, new in (("hvd.attn.proj", "hvd.head"),
                         ("hvd.moe.dispatch", "hvd.exit"),
                         ("hvd.ffn", "hvd.loop")):
            text = text.replace(old, new)
    ctx = t._ctx(monkeypatch, [("jit_hvd_grad", text),
                               ("jit_hvd_apply", t.APPLY)])
    ctx.model = model if model is not None else types.SimpleNamespace()
    return ctx


def test_the_three_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    from chipbench import child
    from horovod_tpu.utils import spans

    read = {m: child.load_reader(m).read for m in NEW_METRICS}
    # 120 ns of required FLOPs at the bf16 peak against 600 ns a step
    model = types.SimpleNamespace(exit_heads_work=lambda: 120e-9 * 197e12)
    ctx = _ctx(monkeypatch, model)
    assert read["exit_heads_ms_per_step"](ctx) == pytest.approx(
        1200 / 1e6 / 2)
    assert read["loop_ms_per_step"](ctx) == pytest.approx(100 / 1e6 / 2)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    assert read["exit_heads_roofline_pct"](ctx) == pytest.approx(
        100.0 * 120 / 600)
    # a model kind that counts no such work: nothing, and no exception
    assert read["exit_heads_roofline_pct"](_ctx(monkeypatch)) is None
    # the sum over the trips and the exits' cotangents: under OUR scopes
    # by the name stacks the compiled grad program carries
    assert spans.read_name_stack(
        "jit(hvd_grad)/transpose(jvp(hvd.loop))/add") == (
        "hvd.loop", "backward")
    assert spans.read_name_stack(
        "jit(hvd_grad)/transpose(jvp(hvd.exit))/rbtd,d->rbt/dot_general"
    ) == ("hvd.exit", "backward")
    assert {"hvd.loop", "hvd.exit"} <= spans.SCOPES


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, metric):
    """A model with no loop (the scopes of another), a program with no
    scope tables at all, and a program from before the loop (its
    ``SCOPES`` does not know the names): None, never 0, no exception."""
    from chipbench import child, scopes
    from horovod_tpu.utils import spans

    model = types.SimpleNamespace(exit_heads_work=lambda: 96.0)
    read = child.load_reader(metric).read
    if metric == "loop_ms_per_step":    # (the others still see hvd.head)
        assert read(_ctx(monkeypatch, model, rename=False)) is None
    ctx = _ctx(monkeypatch, model)
    monkeypatch.setattr(scopes, "program_texts", lambda _ctx: None)
    assert read(ctx) is None
    monkeypatch.setattr(spans, "SCOPES",
                        spans.SCOPES - {"hvd.loop", "hvd.exit"})
    assert read(_ctx(monkeypatch, model)) is None


# The cell's shape in small: two layers of four norms, four trips.
TINY = {
    "kind": "ouro", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6, "total_ut_steps": 4,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_scaling": None, "use_sliding_window": False,
    "layer_types": ["full_attention"] * 2,
    "assumed": {"remat": "attn", "param_dtype": "float32",
                "loss_chunk": 64, "exit_entropy_weight": 0.1,
                "optimizer": {"name": "adam", "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}
LEAVES = {"embed", "final_norm", "lm_head", "exit_gate_w", "exit_gate_b",
          "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm", "wq",
          "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
STACK = LEAVES - {"embed", "final_norm", "lm_head", "exit_gate_w",
                  "exit_gate_b"}
TERMS = {"ce_1", "ce_2", "ce_3", "ce_4", "p_1", "p_2", "p_3", "p_4",
         "entropy"}


def _tiny(control=False):
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    mod = child.load_file("models", "ouro")
    model = (mod.Fp8InTheProgramsPlace if control else mod.Model)(
        TINY, TINY_TRAFFIC)
    # float32 compute: at width 64 bf16's own noise is as large as the
    # chip's bounds, which are set at published widths (PERF.md 2); in
    # float32 the program must meet its reference to rounding.
    model.cfg = dataclasses.replace(model.cfg, dtype="float32")
    return child, mod, lane, model


def test_measure_with_a_tiny_ouro_adapter_checks_every_comparison():
    child, mod, lane, model = _tiny()
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 7,
                      seconds=0.3, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    (flash,) = [s for s in said if s["event"] == "flash_vs_explicit_mask"]
    assert flash["shape"] == [2, 128, 4, 16] and flash["rotated"]
    assert max(flash["err"].values()) < 6e-3
    # the step: the loss, its parts and every leaf in both readings
    (step,) = [s for s in said if s["event"] == "step_vs_reference"]
    assert (step["tokens"], step["on"]) == (256, "the batch trained on")
    assert max(step["err"].values()) < 2e-3, step
    assert set(step["err"]) == {"loss"} | TERMS \
        | {"d_" + x for x in LEAVES} | {"moved_" + x for x in LEAVES} \
        | {"along_" + x for x in STACK}
    # every later exit's gradient comes back through the earlier trips'
    # visits: the first trip's hold most of a shared leaf's gradient
    shares = step["visit_shares"]
    assert len(shares) == 4 and min(shares) > 0 \
        and shares == sorted(shares, reverse=True) and shares[0] > 0.5
    assert sum(step["reference_terms"]["p"]) == pytest.approx(1.0, 1e-5)


def test_fp8_in_the_programs_place_is_refused_by_every_comparison():
    child, mod, lane, model = _tiny(control=True)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=11, seconds=0.2,
                      trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert any(s["event"] == "the_reference_in_fp8_in_the_programs_place"
               for s in said)
    assert mod.COMPARISONS == ("flash", "the step")
    for kind in mod.COMPARISONS:
        assert [f for f in r["faults"] if f.startswith(kind)], kind


def test_the_benchmarks_reference_is_the_programs():
    """Two copies of one model: the benchmark's (blocked, a layer visit
    at a time, its backward sweep written out) and the program's
    (horovod_tpu/models/reference.py) agree on the loss, its parts and
    every gradient leaf to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import llama_init
    from horovod_tpu.models.reference import ouro_loss

    _, mod, _, model = _tiny()
    c = model.cfg
    params = llama_init(c, jax.random.PRNGKey(5))
    params["exit_gate_b"] = jnp.full((1,), 0.3)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0, 128)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    (ref_loss, ref_terms), ref = jax.jit(jax.value_and_grad(
        lambda p: ouro_loss(p, batch, c, terms=True), has_aux=True))(params)
    loss, terms = jax.jit(lambda p: mod.reference_loss(
        mod.reference_params(p, c), batch, c, terms=True))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    for mine, theirs in zip(terms, ref_terms):
        assert jnp.allclose(mine, theirs, rtol=1e-5)
    seen = {}
    swept, _, shares = mod.reference_loss_and_grads(
        params, batch, c, lambda where, g: seen.setdefault(
            where, {}).update(g))
    assert abs(float(swept) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert len(shares) == 4

    def err(g, r):
        return float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))

    for name, g in seen[()].items():
        assert err(g, ref[name]) < 2e-5, name
    assert set(seen[()]) == {"embed", "final_norm", "lm_head",
                             "exit_gate_w", "exit_gate_b"}
    for at in range(c.n_layers):
        for name, g in seen["layers", at].items():
            assert err(g, ref["layers"][name][at]) < 2e-5, (at, name)
