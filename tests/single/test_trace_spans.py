"""The spans the program writes into the profiler's own trace, the names
of what it runs on the device, and its compile counter (docs/metrics.md
"Program spans"). CPU, device plane at size 1, in a worker process: the
trace is taken with ``jax.profiler.start_trace`` and read back with
``jax.profiler.ProfileData``, as ``chipbench/xplane.py`` reads a chip's.
"""

import re

import pytest

from tests.utils_mp import run_ranks

LEAVES = 12


def _hvd_events(trace_dir):
    """[(name, line, start_ns, end_ns, stats)] of every ``hvd.*`` event
    on the ``/host:CPU`` plane; ``line`` tells threads apart."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("hvd."):
                    assert plane.name == "/host:CPU", plane.name
                    out.append((e.name, f"{n}:{line.name}", e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[2])


def _worker_traced(rank, size):
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import xla_ici
    from horovod_tpu.jax.optimizer import allreduce_gradients
    from horovod_tpu.parallel import make_split_train_step
    from horovod_tpu.utils import spans

    steps = [(spans.steps_begun(), spans.steps_returned())]
    hvd.init()
    try:
        assert xla_ici.active()
        marks = [spans.marks()]
        tree = {f"w{i}": jnp.full((8, i + 1), float(i), jnp.float32)
                for i in range(LEAVES)}
        ts = make_split_train_step(
            lambda p, d: jnp.sum((p["w"] * d) ** 2), optax.sgd(0.1))
        batch = jnp.arange(4.0)

        def eager():
            out = allreduce_gradients(tree, op=hvd.Average)
            return [np.asarray(out[k]) for k in sorted(out)]

        def spmd():   # a fresh carry each time: the step donates it
            loss, (params, _) = ts.step(ts.init({"w": jnp.ones(4)}),
                                        batch)
            return [np.asarray(loss), np.asarray(params["w"])]

        untraced = eager()                  # also warms every program
        steps.append((spans.steps_begun(), spans.steps_returned()))
        marks.append(spans.marks())
        untraced += spmd()
        steps.append((spans.steps_begun(), spans.steps_returned()))
        d_eager, d_spmd = tempfile.mkdtemp(), tempfile.mkdtemp()
        jax.profiler.start_trace(d_eager)
        traced = eager()
        jax.profiler.stop_trace()
        jax.profiler.start_trace(d_spmd)
        traced += spmd()
        jax.profiler.stop_trace()
        bit_equal = all(np.array_equal(a, b) and np.array_equal(a, c)
                        for a, b, c in zip(untraced, traced,
                                           eager() + spmd()))
        steps.append((spans.steps_begun(), spans.steps_returned()))
        marks.append(spans.marks())
        return {"eager": _hvd_events(d_eager),
                "spmd": _hvd_events(d_spmd), "bit_equal": bit_equal,
                "bytes": sum(v.nbytes for v in tree.values()),
                "steps": steps, "marks": marks}
    finally:
        hvd.shutdown()


@pytest.fixture(scope="module")
def traced():
    (out,) = run_ranks(_worker_traced, 1, timeout=180,
                       env={"HOROVOD_XLA_DATA_PLANE": "1"})
    return out


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_one_allreduce_of_many_leaves_leaves_one_span_a_stage(traced):
    names = [e[0] for e in traced["eager"]]
    # one dtype -> one fused response -> one program launch
    assert sorted(names) == ["hvd.device_exec", "hvd.enqueue", "hvd.wait"]


def test_device_exec_is_on_the_cores_thread_after_enqueue_began(traced):
    (enq,), (exe,), (wait,) = (_named(traced["eager"], n) for n in (
        "hvd.enqueue", "hvd.device_exec", "hvd.wait"))
    assert enq[1] == wait[1] != exe[1]          # user's thread / core's
    assert enq[2] < exe[2] and enq[3] <= wait[2]
    assert exe[3] <= wait[3]                    # stored before we woke


def test_spans_carry_what_the_table_says(traced):
    (enq,), (exe,) = (_named(traced["eager"], n) for n in (
        "hvd.enqueue", "hvd.device_exec"))
    assert enq[4] == {"tensors": LEAVES, "bytes": traced["bytes"]}
    assert exe[4] == {"op": "allreduce", "tensors": LEAVES,
                      "bytes": traced["bytes"],
                      "executable_cache": "hit"}


def test_split_step_leaves_one_dispatch_span(traced):
    assert [e[0] for e in traced["spmd"]] == ["hvd.spmd.step"]


@pytest.mark.parametrize("lane", ["eager", "spmd"])
def test_at_most_six_program_spans_a_step(traced, lane):
    assert 1 <= len(traced[lane]) <= 6


def test_tracing_changes_no_output(traced):
    assert traced["bit_equal"]


def test_with_no_trace_the_helper_records_nothing():
    from horovod_tpu.utils.spans import SPANS, span

    for name in SPANS:
        with span(name, tensors=1) as s:
            assert not s.is_enabled()
    with pytest.raises(ValueError, match="not a program span"):
        span("hvd.anything")


def test_start_up_marks_are_stamped_once_in_the_order_reached(traced):
    """docs/metrics.md "Set-up: the compile log and the start-up
    marks": the worker reaches every mark but ``hvd.cache.enabled``
    (tests keep the compile cache off)."""
    from horovod_tpu.utils.spans import MARKS

    at_init, after_a_step, at_the_end = traced["marks"]
    order = ["hvd.imported", "hvd.init", "hvd.init.core", "hvd.init.plane",
             "hvd.step.first", "hvd.step.first_dispatched"]
    assert list(at_init) == order[:4]
    assert list(after_a_step) == list(at_the_end) == order
    assert set(order) | {"hvd.cache.enabled"} == MARKS
    seconds = [at_the_end[name] for name in order]
    assert seconds == sorted(seconds) and seconds[0] > 0
    # five more steps and two traces later every mark reads as it did
    assert after_a_step == at_the_end
    assert {k: at_the_end[k] for k in at_init} == at_init


def test_marks_are_a_closed_table(monkeypatch):
    from horovod_tpu.utils import spans

    with pytest.raises(ValueError, match="not a start-up mark"):
        spans.mark("hvd.anything")
    monkeypatch.setattr(spans, "_marks", {})
    spans.mark("hvd.init")
    first = spans.marks()
    spans.mark("hvd.init")                 # reached again: not stamped
    assert spans.marks() == first and list(first) == ["hvd.init"]
    assert 0 < first["hvd.init"] <= spans.since_start()


def test_each_lane_counts_a_step_where_it_begins_and_returns(traced):
    """One ``allreduce_gradients`` or one split step is one step begun
    and one returned: the worker makes three of each."""
    assert traced["steps"] == [(0, 0), (1, 1), (2, 2), (6, 6)]


def test_a_step_under_way_is_begun_and_not_returned():
    from horovod_tpu.parallel import train_step
    from horovod_tpu.utils import spans

    def under_way(carry, batch):
        if batch is None:
            raise RuntimeError("a step that fails")
        return spans.steps_begun() - spans.steps_returned()

    step = train_step._spanned(under_way)
    begun = spans.steps_begun()
    assert step(None, 0) == 1
    with pytest.raises(RuntimeError):
        step(None, None)                   # a failed step has returned
    assert spans.steps_begun() == spans.steps_returned() == begun + 2


@pytest.mark.parametrize("module", [
    "horovod_tpu.utils.spans", "horovod_tpu.utils.compile_cache",
    "horovod_tpu.parallel.train_step", "horovod_tpu.jax.xla_ici"])
def test_writing_a_span_does_not_pull_in_the_telemetry_package(module):
    """The layers that write spans lie below ``horovod_tpu.telemetry``
    (exporters, ledgers, debug server): the helper is a leaf module."""
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "assert not [m for m in sys.modules "
            "if m.startswith('horovod_tpu.telemetry')]")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("microbatches,programs", [(1, 2), (3, 3)])
def test_a_step_makes_no_more_python_calls_for_filing_its_programs(
        microbatches, programs):
    """The jitted programs file themselves (``spans.files_itself``) at
    their first call and stand bare after it: from the second step on
    the host's path through ``step`` is the span, the dispatch and the
    split of the batch, as before PR 36."""
    import sys

    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import make_split_train_step
    from horovod_tpu.utils import spans

    spans._PROGRAMS.clear()
    ts = make_split_train_step(lambda p, d: jnp.sum((p * d) ** 2),
                               optax.sgd(0.1), microbatches=microbatches)
    carry, batch = ts.init(jnp.ones((3, 4))), jnp.ones((3, 4))

    def calls_of_a_step(carry):
        seen = []

        def profile(frame, event, _arg):
            if event == "call" and "horovod_tpu" in frame.f_code.co_filename:
                seen.append(frame.f_code.co_name)

        sys.setprofile(profile)
        try:
            _, carry = ts.step(carry, batch)
        finally:
            sys.setprofile(None)
        return seen, carry

    first, carry = calls_of_a_step(carry)
    second, carry = calls_of_a_step(carry)
    third, carry = calls_of_a_step(carry)
    # grad and apply; with microbatches the accumulating grad too
    assert first.count("first") == programs
    assert len(spans._PROGRAMS) == programs
    # (the two counts of a step, spans.step_begins and step_returns,
    # are the whole of what PR 52 put on this path)
    steady = ["step", "step_begins", "span", "step"] + (
        ["_split_microbatches", "<lambda>"] if microbatches > 1 else []
    ) + ["step_returns"]
    assert sorted(second) == sorted(third)
    assert [c for c in second if c not in ("<lambda>",)] \
        == [c for c in steady if c != "<lambda>"]
    assert "first" not in second and "register_program" not in second
    spans._PROGRAMS.clear()


@pytest.mark.parametrize("program", [
    "grad", "grad_acc", "apply", "allreduce", "allreduce_local",
    "broadcast", "allgather", "alltoall", "reducescatter"])
def test_programs_are_named_for_what_they_are(program):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.common.eager_ops import ReduceOp
    from horovod_tpu.jax import xla_ici
    from horovod_tpu.parallel import make_split_train_step

    x = jnp.ones((3, 4))
    if program in ("grad", "grad_acc", "apply"):
        ts = make_split_train_step(lambda p, d: jnp.sum(p * d),
                                   optax.sgd(0.1), microbatches=3)
        # three microbatches: hvd_grad, hvd_grad_acc twice, hvd_apply
        calls = re.findall(r"name=(\w+)", str(jax.make_jaxpr(ts.step)(
            ts.init(x), x)))
        assert calls.count("hvd_" + program) == {"grad_acc": 2}.get(
            program, 1), calls
        return
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    scales = ((1.0, 1.0),)
    fn, args = {
        "allreduce": lambda: (xla_ici._build_allreduce(
            mesh, 2, [(2, 4)], ReduceOp.SUM, scales), (jnp.ones((4, 4)),)),
        "allreduce_local": lambda: (xla_ici._build_allreduce_local(
            ReduceOp.SUM, scales, False), (x,)),
        "broadcast": lambda: (xla_ici._build_broadcast(mesh, 0),
                              (jnp.ones((2, 8)),)),
        "allgather": lambda: (xla_ici._build_allgather(mesh, (2, 2)),
                              (jnp.ones((2, 2, 4)),)),
        "alltoall": lambda: (xla_ici._build_alltoall(mesh, 2),
                             (jnp.ones((2, 4, 3)),)),
        "reducescatter": lambda: (xla_ici._build_reducescatter(
            mesh, 2, ReduceOp.SUM, (1.0, 1.0), 0, 2),
            (jnp.ones((2, 4, 3)),)),
    }[program]()
    want = "jit_hvd_" + program.replace("_local", "")
    assert f"module @{want} " in fn.lower(*args).as_text()


@pytest.mark.parametrize("kernel", ["hvd_flash_fwd", "hvd_flash_bwd_fused"])
def test_flash_kernels_can_be_told_apart_by_name(kernel, monkeypatch):
    import importlib

    import jax
    import jax.numpy as jnp

    # (the package exports the function under the module's name)
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    k = jnp.ones((1, 128, 1, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    assert "pallas_call" in text
    assert re.search(rf"\b{kernel}\b", text), text[:2000]


def _worker_compile_stats(rank, size):
    import os

    import jax
    import jax.numpy as jnp

    from horovod_tpu.utils.compile_cache import (
        compile_stats,
        enable_compile_cache,
    )

    assert compile_stats()["backend_compiles"] == 0
    assert enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
    enable_compile_cache()                 # listeners register once
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    x = jnp.arange(5.0)
    x.block_until_ready()

    def tiny(v):
        return v * 3 + 1

    seen = [compile_stats()]
    for _ in range(2):
        jax.jit(tiny)(x).block_until_ready()
        jax.clear_caches()                 # forget the executable
        seen.append(compile_stats())
    return seen


def test_compile_stats_counts_one_compile_then_one_cache_hit(tmp_path):
    (seen,) = run_ranks(
        _worker_compile_stats, 1, timeout=120,
        env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    start, first, second = seen

    def delta(a, b, key):
        return b[key] - a[key]

    assert delta(start, first, "backend_compiles") == 1
    assert delta(start, first, "cache_misses") == 1
    assert delta(start, first, "cache_hits") == 0
    assert delta(first, second, "backend_compiles") == 0
    assert delta(first, second, "cache_hits") == 1
    assert first["compile_s"] > start["compile_s"] >= 0
    assert second["cache_retrieval_s"] > first["cache_retrieval_s"]
