"""The readers PR 25 added, on a hand-built trace and counter pair: the
program's own ``hvd.*`` spans per step, the core's counters per step,
the backward flash kernels by name, the compile counter. CPU; no
backend is touched while this file is imported."""

import json
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NEW_CELL = "mistral7b.hvd4.b2s4096"

# One chip, times in ns. The grad program runs at 1000, 2000, 3000 after
# a cut-off first execution, so the window is [1000, 3000]: two steps.
# On the op line three Mosaic calls as the v5e shows them with the
# compile cache on: %tpu_custom_call.N, told apart by their
# kernel_metadata alone (the forward call's OPERAND is named like a
# backward kernel: operands do not count). Two host threads. The user's:
# hvd.enqueue [900, 1100] (the window's edge cuts 100 off), hvd.wait
# [1100, 1500],
# hvd.spmd.step [1500, 1550], hvd.enqueue [2000, 2100], hvd.wait
# [2100, 2400] and [2900, 3200] (cut at 3000). The core's:
# hvd.device_exec [1200, 1300] and [2200, 2250], inside the waits in
# time but on another thread.
XSPACE = r"""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 10 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 700000 }
    events { metadata_id: 10 offset_ps: 2000000 duration_ps: 700000 }
    events { metadata_id: 10 offset_ps: 3000000 duration_ps: 700000 }
  }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 1100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 1400000 duration_ps: 200000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 2400000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 2600000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%tpu_custom_call.26 = (bf16[2,8]{1,0:T(8,128)(2,1)}, f32[2,1]{1,0:T(2,128)}) custom-call(bf16[2,8]{1,0:T(8,128)(2,1)} %hvd_flash_bwd_dq_operand.7), custom_call_target=\"tpu_custom_call\", frontend_attributes={kernel_metadata={\n\"kernel\":\"hvd_flash_fwd\"\n}}" } }
  event_metadata { key: 2 value { id: 2 name: "%tpu_custom_call.28 = (bf16[2,8]{1,0:T(8,128)(2,1)}, bf16[2,8]{1,0:T(8,128)(2,1)}) custom-call(bf16[2,8]{1,0:T(8,128)(2,1)} %p.2), custom_call_target=\"tpu_custom_call\", frontend_attributes={kernel_metadata={\n\"kernel\":\"hvd_flash_bwd_dkv\"\n}}" } }
  event_metadata { key: 3 value { id: 3 name: "%tpu_custom_call.27 = bf16[2,8]{1,0:T(8,128)(2,1)} custom-call(bf16[2,8]{1,0:T(8,128)(2,1)} %p.3), custom_call_target=\"tpu_custom_call\", frontend_attributes={kernel_metadata={\n\"kernel\":\"hvd_flash_bwd_dq\"\n}}" } }
  event_metadata { key: 4 value { id: 4 name: "%hvd_flash_bwd_lookalike.4 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]{1,0:T(8,128)(2,1)} %p.4), kind=kLoop, calls=%fused_computation.4" } }
  event_metadata { key: 10 value { id: 10 name: "jit_hvd_grad(1)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python"
    events { metadata_id: 1 offset_ps: 900000 duration_ps: 200000 }
    events { metadata_id: 3 offset_ps: 1100000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 50000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 2100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 2900000 duration_ps: 300000 }
  }
  lines { id: 2 name: "hvdtpu-core"
    events { metadata_id: 2 offset_ps: 1200000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2200000 duration_ps: 50000 }
  }
  event_metadata { key: 1 value { id: 1 name: "hvd.enqueue" } }
  event_metadata { key: 2 value { id: 2 name: "hvd.device_exec" } }
  event_metadata { key: 3 value { id: 3 name: "hvd.wait" } }
  event_metadata { key: 4 value { id: 4 name: "hvd.spmd.step" } }
}
"""
# The same chip as a program from before PR 25 leaves it: no hvd.* span,
# kernels without metadata.
XSPACE_BEFORE = re.sub(r'\\n\\"kernel\\":\\"hvd_flash_\w+\\"\\n', "",
                       XSPACE.replace("hvd.", "other."))

BEFORE = {"negotiation_us": {"sum_us": 1000}, "cycle": {"overrun_us": 500},
          "cache": {"hits": 10, "misses": 10}}
AFTER = {"negotiation_us": {"sum_us": 9000}, "cycle": {"overrun_us": 2500},
         "cache": {"hits": 13, "misses": 11}}


def _ctx(text, counters):
    from jax.profiler import ProfileData

    from chipbench import xplane

    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    (chip,) = xplane.chips(profile)
    assert (chip.t0, chip.t1, chip.steps) == (1000, 3000, 2)
    return types.SimpleNamespace(chip=chip, profile=profile,
                                 counters=counters, steps_in_window=4)


def _read(name, ctx):
    from chipbench import child

    return child.load_reader(name).read(ctx)


@pytest.mark.parametrize("metric,want", [
    # [900, 1100] as far as it lies in the window, + [2000, 2100]
    ("hvd_enqueue_ms_per_step.cnn", (100 + 100) / 1e6 / 2),
    ("hvd_enqueue_ms_per_step.lm", (100 + 100) / 1e6 / 2),
    # the other thread's device_exec is NOT taken out of the wait; the
    # last wait counts up to the window's end
    ("hvd_wait_ms_per_step.cnn", (400 + 300 + 100) / 1e6 / 2),
    ("hvd_wait_ms_per_step.lm", (400 + 300 + 100) / 1e6 / 2),
    ("hvd_device_exec_ms_per_step.cnn", (100 + 50) / 1e6 / 2),
    ("hvd_device_exec_ms_per_step.lm", (100 + 50) / 1e6 / 2),
    ("spmd_dispatch_ms_per_step.lm", 50 / 1e6 / 2),
    # counters: over the WHOLE window (4 steps), us -> ms
    ("hvd_negotiate_ms_per_step.cnn", 8000 / 1e3 / 4),
    ("hvd_negotiate_ms_per_step.lm", 8000 / 1e3 / 4),
    ("hvd_cycle_overrun_ms_per_step.cnn", 2000 / 1e3 / 4),
    ("hvd_cycle_overrun_ms_per_step.lm", 2000 / 1e3 / 4),
    ("hvd_response_cache_hit_pct.lm", 75.0),
    # dkv 300 + dq 200 a step; not the forward kernel whose operand is
    # named like one, not the fusion that is no kernel
    ("flash_bwd_ms_per_step", (300 + 200) * 2 / 1e6 / 2),
    ("flash_ms_per_step", (100 + 300 + 200) * 2 / 1e6 / 2),
])
def test_new_readers_on_the_hand_built_trace(metric, want):
    assert _read(metric, _ctx(XSPACE, (BEFORE, AFTER))) \
        == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "hvd_enqueue_ms_per_step.cnn", "hvd_wait_ms_per_step.lm",
    "hvd_device_exec_ms_per_step.lm", "spmd_dispatch_ms_per_step.lm",
    "hvd_negotiate_ms_per_step.cnn", "hvd_cycle_overrun_ms_per_step.lm",
    "hvd_response_cache_hit_pct.lm", "flash_bwd_ms_per_step"])
def test_a_program_without_the_span_or_counter_reads_none_not_zero(metric):
    # the spmd lane's counters are {}; the parent's trace has no hvd.*
    assert _read(metric, _ctx(XSPACE_BEFORE, ({}, {}))) is None


def test_no_cache_lookup_in_the_window_is_none_not_zero_percent():
    same = {"cache": {"hits": 0, "misses": 0}}
    ctx = types.SimpleNamespace(counters=(same, same))
    assert _read("hvd_response_cache_hit_pct.lm", ctx) is None
    miss = {"cache": {"hits": 0, "misses": 39}}
    ctx = types.SimpleNamespace(counters=(same, miss))
    assert _read("hvd_response_cache_hit_pct.lm", ctx) == 0.0


def test_setup_compile_s_reads_the_programs_counter_or_nothing(
        monkeypatch):
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "compile_stats",
                        lambda: {"compile_s": 12.5})
    assert _read("setup_compile_s", None) == 12.5
    # a program from before PR 25 has no such function: nothing, no raise
    monkeypatch.delattr(compile_cache, "compile_stats")
    assert _read("setup_compile_s", None) is None


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_four_chip_cell_reports_what_the_issue_lists(bench):
    (cell,) = [w for w in bench["workloads"] if w["name"] == NEW_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mistral-7b", "hvd4.b2s4096", 4)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "hvd4.b2s4096.json")) as f:
        traffic = json.load(f)
    assert {k: traffic[k] for k in (
        "lane", "ranks", "batch", "seq", "warmup_steps",
        "calibration_steps", "traced_steps")} == {
        "lane": "hvd", "ranks": 4, "batch": 2, "seq": 4096,
        "warmup_steps": 2, "calibration_steps": 3, "traced_steps": 5}
    from chipbench import child

    got = {s: {m["name"] for m in child.metrics_of(bench, s, NEW_CELL)}
           for s in ("end_to_end", "per_layer")}
    assert got["end_to_end"] == {"tokens_per_s", "step_ms_p90",
                                 "peak_hbm_gb", "setup_s"}
    assert got["per_layer"] == {
        "device_idle_pct.lm", "flash_ms_per_step",
        "optimizer_ms_per_step.lm", "hvd_enqueue_ms_per_step.lm",
        "hvd_negotiate_ms_per_step.lm", "hvd_device_exec_ms_per_step.lm",
        "hvd_wait_ms_per_step.lm", "hvd_response_cache_hit_pct.lm",
        "hvd_cycle_overrun_ms_per_step.lm", "flash_bwd_ms_per_step",
        "setup_compile_s", "allreduce_exposed_ms_per_step"}


def test_readers_read_only_spans_the_program_writes():
    from horovod_tpu.utils.spans import SPANS

    read = set()
    for name in os.listdir(os.path.join(ROOT, "chipbench",
                                        "layer_metrics")):
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               name)) as f:
            read |= set(re.findall(r'"(hvd\.[a-z_.]+)"', f.read()))
    assert read == SPANS    # and every span is read by some metric
