"""chipbench on the CPU: the trace reduction on a hand-built XSpace, the
manifest's consistency, the refusal to run without a TPU, and the
child's measuring function on a tiny model. No topology is described
and no backend is touched while this file is imported."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# One chip, times in ns (line timestamp 0). The trace begins in the
# middle of an execution of the grad program ([600, 900], cut off), then
# holds three whole ones at 1000, 2000, 3000: the window is [1000, 3000],
# two whole steps, with an apply program in each and an all-reduce in
# the first only. On the op line, named by their whole HLO text as
# the v5e names them: a `while` [1000, 1600] with two body ops nested in
# it, a Mosaic custom call, a custom call that is no kernel (the apply
# step's only op), an all-reduce [1750, 1900] of which [1750, 1800] is
# covered by another op.
XSPACE = r"""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 10 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 700000 }
    events { metadata_id: 11 offset_ps: 1700000 duration_ps: 100000 }
    events { metadata_id: 10 offset_ps: 2000000 duration_ps: 700000 }
    events { metadata_id: 11 offset_ps: 2700000 duration_ps: 100000 }
    events { metadata_id: 10 offset_ps: 3000000 duration_ps: 700000 }
  }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 600000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 250000 }
    events { metadata_id: 3 offset_ps: 1300000 duration_ps: 300000 }
    events { metadata_id: 4 offset_ps: 1600000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 1700000 duration_ps: 100000 }
    events { metadata_id: 6 offset_ps: 1750000 duration_ps: 150000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 600000 }
    events { metadata_id: 4 offset_ps: 2600000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 2700000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 600000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)}) %tuple.0), condition=%cond, body=%body" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[2,8]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[2,8]{1,0:T(8,128)(2,1)} %p.1), kind=kLoop, calls=%fused_computation.2" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3" } }
  event_metadata { key: 4 value { id: 4 name: "%tpu_custom_call.4 = (bf16[2,8]{1,0:T(8,128)(2,1)}, f32[2,1]{1,0:T(2,128)}) custom-call(bf16[2,8]{1,0:T(8,128)(2,1)} %p.2), custom_call_target=\"tpu_custom_call\"" } }
  event_metadata { key: 5 value { id: 5 name: "%custom-call.5 = bf16[2,8]{1,0:T(8,128)(2,1)} custom-call(bf16[2,8]{1,0} %p.3), custom_call_target=\"Sharding\"" } }
  event_metadata { key: 6 value { id: 6 name: "%all-reduce.6 = bf16[2,8]{1,0:T(8,128)(2,1)} all-reduce(bf16[2,8]{1,0:T(8,128)(2,1)} %p.4), replica_groups={{0,1,2,3}}, to_apply=%add" } }
  event_metadata { key: 10 value { id: 10 name: "jit_grad(1)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_apply_fn(2)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main"
    events { metadata_id: 1 offset_ps: 1850000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 2750000 duration_ps: 300000 }
  }
  event_metadata { key: 1 value { id: 1 name: "allreduce" } }
  event_metadata { key: 2 value { id: 2 name: "sync" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))


@pytest.fixture(scope="module")
def chip(profile):
    from chipbench import xplane

    (chip,) = xplane.chips(profile)
    return chip


def test_window_is_whole_steps_of_the_heaviest_program(chip):
    assert chip.anchor == "jit_grad(1)"
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)


def test_idle_share_is_one_minus_the_union(chip):
    # step 1 busy [1000, 1900], step 2 busy [2000, 2800]
    assert chip.busy_ns == 900 + 800
    assert chip.window_ns == 2000
    assert [tuple(g) for g in chip.idle_gaps()] == [(1900, 2000),
                                                    (2800, 3000)]


def test_a_loop_and_its_body_are_counted_once(chip):
    from chipbench import xplane

    by_name = chip.self_ns_by(xplane.short_name)
    # while.1 is 600 long twice; in step 1 its body takes 250 + 300
    assert by_name["while.1 (s32[], bf16[2,8])"] == (600 - 550) + 600
    assert by_name["fusion.2 bf16[2,8]"] == 250
    assert by_name["fusion.3"] == 300
    # all-reduce.6 starts inside custom-call.5 and ends after it:
    # siblings that overlap, each with its own duration
    assert by_name["all-reduce.6 bf16[2,8]"] == 150
    assert sum(by_name.values()) == chip.busy_ns + 50
    assert chip.self_ns_by(xplane.opcode) == {
        "while": 650, "fusion": 550, "custom-call": 400,
        "all-reduce": 150}


def test_exposed_time_of_one_class_against_all_others(chip):
    from chipbench import xplane

    assert chip.class_ns(xplane.is_all_reduce) == 150
    assert chip.exposed_ns(xplane.is_all_reduce) == 100   # [1800, 1900]
    assert chip.class_ns(xplane.is_mosaic_call) == 200   # not call.5
    assert chip.module_ns(lambda m: "apply" in m.name) == 200


def test_idle_gaps_are_named_by_the_host_span_open_at_the_time(profile,
                                                              chip):
    from chipbench import xplane

    spans = xplane.host_spans(profile, ("allreduce", "sync"))
    assert [s[0] for s in spans] == ["allreduce", "sync"]
    named = xplane.name_gaps(chip.idle_gaps(), spans)
    assert named == [["sync", 200 / 1e9], ["allreduce", 100 / 1e9]]


def test_layer_metric_readers_on_the_hand_built_trace(chip):
    import types

    from chipbench import child

    ctx = types.SimpleNamespace(
        chip=chip, lane=types.SimpleNamespace(size=4), steps_in_window=4,
        counters=({"device_ops": {"allreduce": {"responses": 2}}},
                  {"device_ops": {"allreduce": {"responses": 10}}}))
    read = {n: child.load_reader(n).read(ctx) for n in (
        "device_idle_pct.lm", "device_idle_pct.cnn", "flash_ms_per_step",
        "optimizer_ms_per_step.lm", "allreduce_exposed_ms_per_step",
        "hvd_programs_per_step.cnn")}
    assert read == pytest.approx({"device_idle_pct.lm": 15.0,
                    "device_idle_pct.cnn": 15.0,
                    "flash_ms_per_step": 200 / 1e6 / 2,
                    "optimizer_ms_per_step.lm": 200 / 1e6 / 2,
                    "allreduce_exposed_ms_per_step": 100 / 1e6 / 2,
                    "hvd_programs_per_step.cnn": 2.0})


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_in_the_manifest_resolves_to_a_file(bench):
    kinds, lanes = set(), set()
    for c in bench["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path), c["file"]
        with open(path) as f:
            cfg = json.load(f)
        assert {"source", "reduced", "assumed", "kind"} <= set(cfg)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        kinds.add(cfg["kind"])
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        path = os.path.join(ROOT, "chipbench", "traffic",
                            w["traffic"] + ".json")
        assert os.path.isfile(path), path
        with open(path) as f:
            traffic = json.load(f)
        assert traffic["ranks"] == w["chips"]
        lanes.add(traffic["lane"])
    for kind, names in (("models", kinds), ("lanes", lanes)):
        for n in names:
            assert os.path.isfile(os.path.join(
                ROOT, "chipbench", kind, n + ".py")), (kind, n)
    from chipbench import child

    for m in bench["per_layer"]:   # its own reader, or its stem's
        assert callable(child.load_reader(m["name"]).read), m["name"]
    assert {c["name"] for c in bench["configs"]} \
        == {w["config"] for w in bench["workloads"]}


def test_metrics_cells_and_names_keep_the_contract(bench):
    cells = [w["name"] for w in bench["workloads"]]

    def cells_of(m):
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
        return set(m.get("workloads", cells))

    e2e = {m["name"]: cells_of(m) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert cells_of(m) <= e2e[m["moves"]], m["name"]
    for cell in cells:   # set-up, one more end-to-end, one per-layer
        assert sum(cell in c for c in e2e.values()) >= 2
        assert any(cell in cells_of(m) for m in bench["per_layer"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    names += [w[k] for w in bench["workloads"] for k in ("config",
                                                         "traffic")]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in bench[k]]
        assert len(got) == len(set(got))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    assert 1 <= bench["run_seconds"] <= 51


def test_widths_are_the_published_ones():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral-7b.json")) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "rope_theta")} == {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "vocab_size": 32768, "rope_theta": 1e6}
    assert list(cfg["reduced"]) == ["num_hidden_layers"]


def test_peaks_are_keyed_by_kind_and_an_unknown_kind_is_an_error():
    from chipbench import peaks

    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    # Mistral-7B at 4 layers, T 4096: 6 x the matmul parameters (all but
    # the embedding table and the norm gains) + causal attention.
    n = peaks.lm_matmul_params(4096, 14336, 32, 8, 128, 4, 32768)
    assert n == 4 * 218103808 + 4096 * 32768
    assert peaks.lm_train_flops_per_token(
        4096, 14336, 32, 8, 128, 4, 32768, 4096) \
        == 6 * n + 6 * 4 * 4096 * 4096


def test_run_without_a_tpu_fails_with_a_clear_message(bench):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


TINY_LM = {"kind": "lm", "vocab_size": 256, "hidden_size": 64,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "intermediate_size": 128,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "assumed": {"remat": "attn+gate", "param_dtype": "float32",
                       "optimizer": {"name": "adam",
                                     "learning_rate": 3e-3}}}
TINY_TRAFFIC = {"lane": "spmd", "ranks": 1, "batch": 2, "seq": 128,
                "warmup_steps": 2, "calibration_steps": 2,
                "traced_steps": 0}


def test_measure_with_a_tiny_adapter_returns_the_contracts_keys():
    from chipbench import child

    lane = child.load_file("lanes", "spmd").Lane(TINY_TRAFFIC)
    lane.start()
    model = child.load_file("models", "lm").Model(TINY_LM, TINY_TRAFFIC)
    said = []
    r = child.measure(model, lane, TINY_TRAFFIC, seed=2 ** 31 + 77,
                      seconds=0.5, trace=False, t0=time.time(),
                      say=lambda **k: said.append(k))
    assert r["faults"] == [] and r["failed"] == 0
    assert r["attempted"] >= 6
    assert set(r["end_to_end"]) == {"tokens_per_s", "step_ms_p90",
                                    "peak_hbm_gb", "setup_s"}
    assert r["end_to_end"]["tokens_per_s"] > 0
    # The tail is the tail of every step of the window.
    window = next(s for s in said if s["event"] == "window")
    assert window["intervals"] == r["attempted"] \
        == len(window["step_ms_all"])
    assert all(0 <= step < r["attempted"] and ms >= 1.0 for step, _, ms
               in window["gc_pauses_step_generation_ms"])
    assert r["end_to_end"]["step_ms_p90"] == pytest.approx(
        child.p90(window["step_ms_all"]), rel=1e-3)
    assert r["device"]["platform"] == "cpu"   # named for what it is
    assert {"built", "calibrated", "window", "logits_vs_reference"} \
        <= {s["event"] for s in said}
    cell = {"name": "c", "chips": 1}
    bench = {"end_to_end": [
        {"name": "tokens_per_s", "unit": "tokens/s"},
        {"name": "images_per_s", "unit": "images/s", "workloads": ["x"]},
        {"name": "setup_s", "unit": "s"}]}
    from chipbench import run

    line = run.merge([r], cell, bench, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert set(line["metrics"]["setup_s"]) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
