"""The device step by the program's own scopes: every event of a chip's
``XLA Ops`` line joined, by the name of its instruction, to the scope
the program traced that instruction under (``horovod_tpu/utils/
spans.py``: ``scope``, ``scope_table``; docs/metrics.md "Device
scopes"), and reduced to SELF time by scope x phase x kind of op.

The join. A device trace names an op's event by its instruction
(``%fusion.350 = ...``) and a program's execution on the line ``XLA
Modules`` by its module (``jit_hvd_grad(<id>)``). Each op event belongs
to the module event that encloses its start; the module's name less the
``(<id>)`` picks the scope table of that program, the instruction's name
the row. Two programs may both hold a ``%fusion.7``: they are kept
apart by the module. The tables come from the program
(``spans.scope_tables()``: the programs it jits file themselves); the
two programs the eager lane jits ITSELF (``lanes/hvd.py:build``: the
grad program and the apply program) are lowered here a second time, from
a COPY of the lane's expressions (:func:`_eager_lane_programs`: the
lambda's shape, ``donate_argnums``, ``compiler_options``, the sharding),
and the compile cache answers. That copy depends on ``lanes/hvd.py``
staying as it is; the ``benchmark`` PR that may edit the lane has it
scope and file its own programs (``spans.scope`` round ``apply_fn``,
``spans.register_program``) and deletes the copy. Until then the join is
guarded, never trusted: where the copy compiles anything anew (the
compile cache did not know it, so it is not what ran), or a module of
the window runs an instruction its table does not hold, every reader of
the run reads ``None`` and the ``scopes`` line says why (``refused``).

The arithmetic is ``xplane.Chip``'s, made exact: over the window of
whole steps every instant of the chip's busy time goes to the innermost
op running (:func:`innermost_ns`), so a ``while`` and its body count
once and the rows sum to ``busy_ns`` to the nanosecond. An instruction
that resolves to no scope is a row of its own (scope ``None``), never
dropped: the coverage is what is left beside it.

A program from before the scopes (no ``spans.scope_tables``) gives
``None`` from every reader, never 0 and never an error.

``python3 chipbench/scopes.py --report <trace> <module.hlo.txt>...``
prints the whole table of a kept trace: a run with
``CHIPBENCH_KEEP_TRACE=<dir>`` leaves the trace there and, beside it,
each program's compiled text.
"""

import bisect
import collections
import functools
import heapq
import json
import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import xplane

_FUSION_KIND = re.compile(r"kind=(k\w+)")
Row = collections.namedtuple("Row", "module scope phase kind mixed")


def instruction(ev):
    """``%fusion.350 = bf16[...] fusion(...)`` -> ``fusion.350``."""
    return ev.name.partition(" = ")[0].lstrip("%")


def kind_of(ev):
    """The kind of op, one step finer than ``xplane.opcode``: a fusion
    by its kind (``fusion:kLoop``), a Mosaic kernel as ``mosaic``."""
    op = xplane.opcode(ev)
    if op == "fusion":
        m = _FUSION_KIND.search(ev.name)
        return "fusion:" + m.group(1) if m else op
    return "mosaic" if xplane.is_mosaic_call(ev) else op


def module_name(event_name):
    """``jit_hvd_grad(8701128238738954348)`` -> ``jit_hvd_grad``."""
    return event_name.split("(", 1)[0]


def innermost_ns(events):
    """[(event, ns)]: every instant of the events' union given to ONE
    event, the one that began last among those running (of two that
    began together, the one that ends first), so the values sum to the
    union to the nanosecond. Where events nest this is
    ``xplane.self_times``' self time; where two overlap and neither
    encloses the other (an async copy's ``done`` beside the next op)
    self time counts the overlap twice, and this gives it to the later
    one. ``events`` sorted by (start, -end)."""
    own, running, i, n = [0] * len(events), [], 0, len(events)
    t = events[0].start if events else 0
    while True:
        while i < n and events[i].start <= t:
            heapq.heappush(running, (-events[i].start, events[i].end, i))
            i += 1
        while running and running[0][1] <= t:
            heapq.heappop(running)
        if not running:
            if i >= n:
                return list(zip(events, own))
            t = events[i].start
            continue
        _, end, k = running[0]
        until = min(end, events[i].start) if i < n else end
        own[k] += until - t
        t = until


class StaleTables(LookupError):
    """A module of the window ran an instruction that the table under
    its name does not hold: the table is of another program."""


def reduce_chip(chip, tables):
    """-> ({Row: self ns}, {(module, instruction): self ns} of the ops
    that resolve to no scope). ``tables``: {module name: scope table}.
    The rows sum to ``chip.busy_ns``. A module no table knows is
    unscoped; one whose table lacks an instruction it ran raises
    :class:`StaleTables`."""
    modules = [m for m in chip.modules
               if m.end > chip.t0 and m.start < chip.t1]
    starts = [m.start for m in modules]
    rows = collections.defaultdict(float)
    unscoped = collections.defaultdict(float)
    absent = collections.defaultdict(list)
    for ev, ns in innermost_ns(chip.ops_in_window()):
        i = bisect.bisect_right(starts, ev.start) - 1
        # an op the window's edge cut starts at the edge; its module
        # began before it
        module = module_name(modules[i].name) \
            if i >= 0 and ev.start < modules[i].end else None
        name = instruction(ev)
        scoped = tables.get(module, {}).get(name)
        if scoped is None and module in tables:
            absent[module].append(name)
        scope, phase, mixed = scoped if scoped else (None, "forward", False)
        rows[Row(module, scope, phase, kind_of(ev), mixed)] += ns
        if scope is None:
            unscoped[module, name] += ns
    if absent:
        raise StaleTables("; ".join(
            f"{m}: {len(set(names))} instructions not in its table "
            f"({', '.join(sorted(set(names))[:4])})"
            for m, names in sorted(absent.items())))
    return dict(rows), dict(unscoped)


# ---------------------------------------------------------------------
# The tables of a run


def _eager_lane_programs(ctx):
    """The two programs ``lanes/hvd.py`` jits itself, as it builds them:
    the same expressions, names and jit options over the abstract
    signature of ``model.init`` / ``model.batch``, committed to this
    rank's chip as the lane commits them."""
    import jax
    import jax.numpy as jnp
    import optax

    model = ctx.model
    dev = jax.local_devices()[0]
    here = jax.sharding.SingleDeviceSharding(dev)
    jit_kwargs = {"compiler_options": model.compiler_options} \
        if dev.platform == "tpu" and model.compiler_options else {}

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=here), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params, state = placed(jax.eval_shape(model.init, key))
    batch = placed(jax.eval_shape(model.batch, key))
    tx = model.optimizer(ctx.lane.size)
    opt = placed(jax.eval_shape(tx.init, params))

    grad_fn = jax.jit(
        lambda p, s, d: jax.value_and_grad(model.loss, has_aux=True)(
            p, s, d), **jit_kwargs)

    @functools.partial(jax.jit, donate_argnums=(1, 2), **jit_kwargs)
    def apply_fn(grads, params, opt):
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt

    # gradients have the parameters' shapes and dtypes
    return [(grad_fn, (params, state, batch)),
            (apply_fn, (params, params, opt))]


class NotWhatRan(LookupError):
    """The copy of the eager lane's programs compiled something anew."""


def _compile_stats():
    """What the compile cache did for this process so far (all 0 where
    it is off: nothing is counted then)."""
    try:
        from horovod_tpu.utils.compile_cache import compile_stats

        return compile_stats()
    except ImportError:
        return collections.defaultdict(int)


def _compiled_anew():
    """Programs this process compiled that the compile cache did not
    hold (with the cache off :class:`StaleTables` is the guard left)."""
    return _compile_stats()["backend_compiles"]


def program_texts(ctx):
    """[(module name, compiled text)] of the programs of this run, or
    None for a program that has no scope tables."""
    try:
        from horovod_tpu.utils import spans

        texts = list(spans.program_texts())
    except (ImportError, AttributeError):
        return None
    if getattr(ctx, "traffic", None) and ctx.traffic.get("lane") == "hvd":
        before = _compiled_anew()
        texts += [spans.named(jitted.lower(*abstract).compile().as_text())
                  for jitted, abstract in _eager_lane_programs(ctx)]
        if _compiled_anew() != before:
            raise NotWhatRan(
                "the copy of lanes/hvd.py's grad and apply programs "
                "missed the compile cache: the lane ran other programs")
    return texts


def reduction(ctx):
    """This run's ``(rows, unscoped)`` (:func:`reduce_chip`), computed
    once a run and kept on ``ctx``; None for a program without scope
    tables, a trace without whole steps, or tables that are not of the
    programs that ran (:class:`NotWhatRan`, :class:`StaleTables`). The
    first call prints one line, ``{"event": "scopes", ...}``: the table
    in ms a step, what it cost, what the compile cache answered; or
    ``refused`` and why."""
    if hasattr(ctx, "scope_reduction"):
        return ctx.scope_reduction
    ctx.scope_reduction = None
    if not ctx.chip.steps:
        return None
    rank = getattr(ctx.lane, "rank", 0)
    t0, before = time.perf_counter(), _compile_stats()
    try:
        texts = program_texts(ctx)
        if texts is None:
            return None
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            for i, (name, text) in enumerate(texts):
                with open(os.path.join(
                        keep, f"rank{rank}.{i}.{name}.hlo.txt"), "w") as f:
                    f.write(text)
        from horovod_tpu.utils.spans import tables_of

        tables = tables_of(texts)
        t1, after = time.perf_counter(), _compile_stats()
        got = reduce_chip(ctx.chip, tables)
    except (NotWhatRan, StaleTables) as e:
        print(json.dumps({"event": "scopes", "rank": rank,
                          "refused": f"{type(e).__name__}: {e}"}),
              flush=True)
        return None
    ctx.scope_reduction = got
    t2 = time.perf_counter()
    print(json.dumps({
        "event": "scopes", "rank": rank,
        "programs": sorted(tables), "tables_s": t1 - t0,
        "reduction_s": t2 - t1,
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "cache_misses": after["cache_misses"] - before["cache_misses"],
        **summary(ctx.chip, *got)}), flush=True)
    return got


# ---------------------------------------------------------------------
# What the readers read (``layer_metrics/*.py``: one thin reader each)


def ms_per_step(ctx, *scopes, phase=None):
    """Self time of the ops under any of ``scopes`` (none given: every
    op, the unscoped too) in ``phase`` (all where None), ms a traced
    step; None where nothing matches or there is no table.

    A scope is named WITHOUT its ``hvd.`` (``"attn.proj"``), for one
    reason: ``tests/chipbench/test_program_spans.py::
    test_readers_read_only_spans_the_program_writes`` takes every
    ``"hvd.*"`` string under ``layer_metrics/`` for a host span, and is
    not this PR's to edit. The ``benchmark`` PR that teaches that scan
    the scopes drops the indirection. Until then the table stays closed
    on this side too: a name that is no scope raises, where it has a
    table to ask (on a program from before the scopes every reader
    reads None)."""
    try:
        from horovod_tpu.utils.spans import SCOPES
    except ImportError:
        return None
    wanted = {"hvd." + s for s in scopes}
    if wanted - SCOPES:
        raise ValueError(f"no device scopes: {sorted(wanted - SCOPES)}")
    got = reduction(ctx)
    if got is None:
        return None
    ns = sum(v for row, v in got[0].items()
             if (not wanted or row.scope in wanted)
             and (phase is None or row.phase == phase))
    return ns / 1e6 / ctx.chip.steps if ns else None


def coverage_pct(ctx):
    """The share of the chip's busy time whose instruction resolves to a
    scope, in percent."""
    got = reduction(ctx)
    if got is None or not ctx.chip.busy_ns:
        return None
    scoped = sum(v for row, v in got[0].items() if row.scope is not None)
    return 100.0 * scoped / ctx.chip.busy_ns


def summary(chip, rows, unscoped, top=20):
    """The table as the ``scopes`` line and ``--report`` show it: ms a
    step by scope x phase, by scope x kind, the mixed share, the
    heaviest unscoped instructions, and a program at a time what the
    module line gives it, what its ops' rows hold and how much of that
    is scoped (a program's time on the module line is its ops' and the
    gaps between them)."""
    per = 1e6 * max(chip.steps, 1)
    by_phase = collections.defaultdict(lambda: collections.defaultdict(float))
    by_kind = collections.defaultdict(lambda: collections.defaultdict(float))
    mixed = 0.0
    for row, ns in rows.items():
        by_phase[str(row.scope)][row.phase] += ns / per
        by_kind[str(row.scope)][row.kind] += ns / per
        mixed += ns if row.mixed else 0.0
    total = sum(rows.values())
    programs = {}
    for name in sorted({str(r.module) for r in rows}):
        mine = {r: ns for r, ns in rows.items() if str(r.module) == name}
        programs[name] = {
            "module_line_ms": chip.module_ns(
                lambda m: module_name(m.name) == name) / per,
            "rows_ms": sum(mine.values()) / per,
            "scoped_ms": sum(ns for r, ns in mine.items()
                             if r.scope is not None) / per}
    return {
        "steps": chip.steps, "busy_ms_per_step": chip.busy_ns / per,
        "rows_ms_per_step": total / per,
        "rows_minus_busy_ns": total - chip.busy_ns,
        "coverage_pct": 100.0 * sum(
            v for r, v in rows.items() if r.scope is not None)
        / max(chip.busy_ns, 1),
        "mixed_pct": 100.0 * mixed / max(chip.busy_ns, 1),
        "programs_ms": programs,
        "scope_x_phase_ms": {s: dict(p) for s, p in sorted(
            by_phase.items(), key=lambda kv: -sum(kv[1].values()))},
        "scope_x_kind_ms": {s: dict(sorted(
            k.items(), key=lambda kv: -kv[1])[:6])
            for s, k in by_kind.items()},
        "unscoped_ms": [[m, n, ns / per] for (m, n), ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]]}


def _report(trace, hlo_files):
    from horovod_tpu.utils import spans

    texts = []
    for path in hlo_files:
        with open(path) as f:
            texts.append(spans.named(f.read()))
    tables = spans.tables_of(texts)
    for chip in xplane.chips(xplane.load(trace)):
        if not chip.busy_ns:
            continue
        s = summary(chip, *reduce_chip(chip, tables), top=40)
        print(f"CHIP {chip.name}: {chip.steps} steps, busy "
              f"{s['busy_ms_per_step']:.3f} ms a step, rows "
              f"{s['rows_ms_per_step']:.3f} (rows - busy = "
              f"{s['rows_minus_busy_ns']:.0f} ns), coverage "
              f"{s['coverage_pct']:.2f}%, mixed {s['mixed_pct']:.2f}%")
        print(f"  {'scope':<18}" + "".join(f"{p:>12}" for p in (
            "forward", "recomputed", "backward", "all")))
        for scope, phases in s["scope_x_phase_ms"].items():
            vals = [phases.get(p, 0.0)
                    for p in ("forward", "recomputed", "backward")]
            print(f"  {scope:<18}" + "".join(
                f"{v:12.3f}" for v in vals + [sum(vals)]))
        for name, p in s["programs_ms"].items():
            print(f"  program {name}: module line "
                  f"{p['module_line_ms']:.3f} ms a step, its ops "
                  f"{p['rows_ms']:.3f}, scoped {p['scoped_ms']:.3f}")
        print("  by kind of op, ms a step:")
        for scope, kinds in s["scope_x_kind_ms"].items():
            print(f"  {scope:<18} " + ", ".join(
                f"{k} {v:.3f}" for k, v in kinds.items()))
        print("  heaviest unscoped instructions, ms a step:")
        for module, name, ms in s["unscoped_ms"]:
            print(f"  {ms:10.3f}  {module}  {name}")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--report":
        _report(sys.argv[2], sys.argv[3:])
    else:
        raise SystemExit(__doc__)
