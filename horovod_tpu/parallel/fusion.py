"""Jit-lane compute/collective fusion (docs/fusion.md).

The split train step runs gradient compute and the ZeRO-1 collective
phase as SEPARATE programs: every per-bucket reduce-scatter sits after
the last backward flop, so the wire is fully exposed — the jit lane's
``overlap_efficiency`` reads ~0 while the eager lane (r11) already
hides RS/AG under compute. "Fused Computation-Collective Operations"
(arXiv:2305.06942) is the fix this module implements for the jitted
lane: emit each bucket's reduce-scatter -> (cross-plane psum) ->
shard-adam -> all-gather chain at its earliest dataflow-ready point,
interleaved with the REMAINING backward computation, so the
latency-hiding scheduler (XLA on TPU; the async host ring on the CPU
substrate) overlaps wire with flops.

Three layers, bottom up:

1. **Jaxpr scheduling** — :func:`interleave_collectives` reorders a
   traced program's equations: collective chains (each collective, its
   transitive consumers, and the pure data-movement producers that
   exist only to feed it — the bucket pack chains) float to the
   earliest point their inputs are ready, while every other equation
   keeps its original order. The result is topologically valid by
   construction and the same equations in a different schedule; hvdlint
   C7 (``analysis/checks.py``) verifies the interleaving statically.

2. **Program segmentation** — :func:`segment_closed_jaxpr` splits a
   traced gradient program into runnable sub-programs at bucket-
   readiness boundaries (:func:`grad_bucket_cuts`), so a host-side
   step loop can issue eager per-bucket collectives BETWEEN compute
   segments — the eager-lane overlap recipe applied to a jitted
   backward (``hvd.make_fused_train_step``).

3. **The fused ZeRO-1 step** — :func:`make_fused_zero_programs` builds
   the one-program grad+apply step for
   ``make_split_train_step(zero=..., fusion on)``: value_and_grad +
   bucket pack + the :func:`~horovod_tpu.parallel.zero.
   build_zero_apply_inner` collective pipeline traced as ONE jaxpr
   (``axis_env`` — collectives stay visible), reordered by (1), and
   executed through ``_zero_spmd`` exactly like the unfused apply. On
   multi-slice layouts the cross-plane psum rides inside each bucket's
   chain, so the expensive hop is scheduled under intra-slice compute.

``HOROVOD_JIT_FUSION=0`` (env, or ``hvd.init(jit_fusion=False)``)
restores the unfused two-program split step; the knob changes the
SCHEDULE, never the math — pinned bit-identical by
``tests/parallel/test_fusion.py``.
"""

import dataclasses
import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.extend import core as _jcore


# ---- the fusion knob -------------------------------------------------

_ENV = "HOROVOD_JIT_FUSION"
_override = None  # tri-state: None = follow the env


def set_jit_fusion(enabled):
    """Programmatic override of ``HOROVOD_JIT_FUSION`` (the
    ``hvd.init(jit_fusion=...)`` kwarg lands here). ``None`` restores
    env-driven behavior."""
    global _override
    _override = None if enabled is None else bool(enabled)


def jit_fusion_enabled():
    """Whether jit-lane compute/collective fusion is on (default: yes).

    ``HOROVOD_JIT_FUSION=0`` is the escape hatch back to the unfused
    split step — schedule-identical to the pre-fusion lane, for
    bisection when a substrate miscompiles the interleaved program."""
    if _override is not None:
        return _override
    return os.environ.get(_ENV, "1").strip().lower() not in (
        "0", "false", "off", "no")


# ---- jaxpr scheduling ------------------------------------------------

#: named-axis collective primitives (the same family
#: ``analysis.extract.COLLECTIVE_PRIMS`` walks).
COLLECTIVE_PRIM_NAMES = frozenset({
    "psum", "pmax", "pmin", "psum_scatter", "reduce_scatter",
    "all_gather", "all_to_all", "ppermute", "pbroadcast", "pgather",
})

#: pure data-movement primitives: zero flops, so hoisting them along
#: with the collective they feed (bucket pack chains are
#: zeros + dynamic_update_slice + reshape/astype) never reorders any
#: arithmetic relative to other arithmetic.
_MOVEMENT_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "convert_element_type", "squeeze",
    "expand_dims", "transpose", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "copy", "rev",
})


def _graph(eqns):
    """(deps, consumers) adjacency over equation indices."""
    producer = {}
    for i, e in enumerate(eqns):
        for v in e.outvars:
            producer[v] = i
    deps = [set() for _ in eqns]
    consumers = [[] for _ in eqns]
    for i, e in enumerate(eqns):
        for v in e.invars:
            if hasattr(v, "count") and v in producer:
                j = producer[v]
                if j != i and j not in deps[i]:
                    deps[i].add(j)
                    consumers[j].append(i)
    return deps, consumers


def collective_chains(eqns):
    """Indices of the equations that belong to a collective chain: each
    collective itself, its transitive consumers (shard update, gather,
    unpack — everything downstream of the first collective is chain
    work), and its pure data-movement ancestors (the pack copies whose
    only job is assembling the collective's operand)."""
    deps, consumers = _graph(eqns)
    colls = [i for i, e in enumerate(eqns)
             if e.primitive.name in COLLECTIVE_PRIM_NAMES]
    marked = set(colls)
    stack = list(colls)
    while stack:  # forward cone: every consumer of chain output
        for j in consumers[stack.pop()]:
            if j not in marked:
                marked.add(j)
                stack.append(j)
    def _hoistable(e):
        # Pure data movement, or negligible scalar math (the adam
        # bias-correction / axis_index offset feeders): moving these
        # never reorders real arithmetic relative to real arithmetic.
        if e.primitive.name in _MOVEMENT_PRIMS:
            return True
        sizes = [v.aval.size for v in e.outvars
                 if hasattr(getattr(v, "aval", None), "size")]
        return bool(sizes) and max(sizes) <= 64

    stack = list(marked)
    seen = set(marked)
    while stack:  # backward cone: the pack/slice/scalar feeder chains
        for j in deps[stack.pop()]:  # that exist only to feed the chain
            if j in seen:
                continue
            seen.add(j)
            if _hoistable(eqns[j]):
                marked.add(j)
                stack.append(j)
    return marked


def interleave_collectives(closed):
    """Reschedule a ``ClosedJaxpr``: collective chains move to their
    earliest dataflow-ready points; every other equation keeps its
    original relative order. Math is untouched — same equations, same
    dataflow, different emission order — so XLA sees each
    reduce-scatter BEFORE the remaining backward flops and can overlap
    the wire under them. Returns ``closed`` unchanged when there is
    nothing to move (no collectives, or already interleaved)."""
    jaxpr = closed.jaxpr
    eqns = list(jaxpr.eqns)
    marked = collective_chains(eqns)
    if not marked:
        return closed
    deps, _ = _graph(eqns)
    emitted = [False] * len(eqns)
    order = []
    pending = sorted(marked)

    def flush():
        progressed = True
        while progressed:
            progressed = False
            still = []
            for i in pending:
                if all(emitted[j] for j in deps[i]):
                    emitted[i] = True
                    order.append(i)
                    progressed = True
                else:
                    still.append(i)
            pending[:] = still

    for i in range(len(eqns)):
        if i in marked:
            continue
        flush()  # everything ready goes BEFORE the next compute eqn
        emitted[i] = True
        order.append(i)
    flush()
    assert not pending and len(order) == len(eqns), "cyclic jaxpr?"
    if order == list(range(len(eqns))):
        return closed
    reordered = _jcore.Jaxpr(jaxpr.constvars, jaxpr.invars,
                             jaxpr.outvars, [eqns[i] for i in order],
                             jaxpr.effects)
    return _jcore.ClosedJaxpr(reordered, closed.consts)


# ---- program segmentation (the host-lane overlap vehicle) ------------

@dataclasses.dataclass(frozen=True)
class Segment:
    fn: Any          # jitted callable over ``in_vars`` values
    in_vars: tuple   # jaxpr Vars consumed (from env)
    out_vars: tuple  # jaxpr Vars produced (into env)


@dataclasses.dataclass(frozen=True)
class SegmentedProgram:
    """A traced program split into sequentially runnable jits.

    ``run`` threads an environment of jaxpr-var -> value through the
    segments; ``on_boundary(k, env)`` fires after segment ``k`` is
    DISPATCHED (jax async dispatch — its outputs are futures), which is
    exactly where the host step loop issues the eager collectives for
    the gradient buckets that segment completed: the remaining
    segments keep computing while the wire drains the finished buckets.
    """

    segments: tuple
    invars: tuple
    outvars: tuple
    const_env: Any   # dict of constvar -> value

    def run(self, *args, on_boundary=None):
        env = dict(self.const_env)
        env.update(zip(self.invars, args))
        for k, seg in enumerate(self.segments):
            outs = seg.fn(*(env[v] for v in seg.in_vars))
            env.update(zip(seg.out_vars, outs))
            if on_boundary is not None:
                on_boundary(k, env)
        return [v.val if isinstance(v, _jcore.Literal) else env[v]
                for v in self.outvars], env

    def read_output(self, env, position):
        v = self.outvars[position]
        return v.val if isinstance(v, _jcore.Literal) else env[v]


def segment_closed_jaxpr(closed, cuts, jit_kwargs=None):
    """Split ``closed`` at equation indices ``cuts`` (ascending,
    exclusive prefix lengths) into a :class:`SegmentedProgram`. Each
    segment is its own jit over exactly the live values crossing its
    boundaries; running the segments back-to-back runs the original
    program's equations in their order. Each segment is an XLA program
    of its own, so its fusions (a multiply-add contracted into one
    rounding) are not those of the whole program or of an eager call:
    values agree to the last place or two, bit for bit only when both
    run a primitive at a time (tests/single/test_fusion_pass.py pins
    both)."""
    jaxpr = closed.jaxpr
    eqns = list(jaxpr.eqns)
    cuts = [c for c in sorted(set(cuts)) if 0 < c < len(eqns)]
    bounds = [0, *cuts, len(eqns)]
    ranges = list(zip(bounds[:-1], bounds[1:]))
    jk = dict(jit_kwargs or {})

    seg_use, seg_def = [], []
    for a, b in ranges:
        use, use_set, defs = [], set(), set()
        for e in eqns[a:b]:
            for v in e.invars:
                if (hasattr(v, "count") and v not in defs
                        and v not in use_set):
                    use.append(v)
                    use_set.add(v)
            for v in e.outvars:
                defs.add(v)
        seg_use.append(use)
        seg_def.append(defs)

    # used_later[k]: vars needed strictly after segment k (or outputs).
    acc = {v for v in jaxpr.outvars if hasattr(v, "count")}
    used_later = [None] * len(ranges)
    for k in reversed(range(len(ranges))):
        used_later[k] = set(acc)
        acc |= set(seg_use[k])

    segments = []
    for k, (a, b) in enumerate(ranges):
        out_vars = []
        seen = set()
        for e in eqns[a:b]:
            for v in e.outvars:
                if v in used_later[k] and v not in seen:
                    out_vars.append(v)
                    seen.add(v)
        effects = set()
        for e in eqns[a:b]:
            effects |= set(getattr(e, "effects", ()))
        sub = _jcore.Jaxpr((), tuple(seg_use[k]), tuple(out_vars),
                           eqns[a:b], frozenset(effects))
        fn = jax.jit(_jcore.jaxpr_as_fun(_jcore.ClosedJaxpr(sub, ())),
                     **jk)
        segments.append(Segment(fn=fn, in_vars=tuple(seg_use[k]),
                                out_vars=tuple(out_vars)))
    return SegmentedProgram(
        segments=tuple(segments), invars=tuple(jaxpr.invars),
        outvars=tuple(jaxpr.outvars),
        const_env=dict(zip(jaxpr.constvars, closed.consts)))


def grad_bucket_cuts(closed, layout, grad_out_start=1):
    """Bucket-readiness cut points for a traced gradient program whose
    outputs are ``(loss, *grad_leaves)`` (``grad_out_start`` skips the
    loss). Returns ``(cuts, ready)``: ``cuts`` are the equation indices
    where at least one bucket's gradient leaves are all produced
    (feed :func:`segment_closed_jaxpr`), ``ready[b]`` the cut each
    bucket completes at — ``sorted(range(n), key=ready.__getitem__)``
    is the wire issue order."""
    eqns = closed.jaxpr.eqns
    producer = {}
    for i, e in enumerate(eqns):
        for v in e.outvars:
            producer[v] = i
    ready = []
    for b in layout.buckets:
        r = 0
        for li in b.indices:
            v = closed.jaxpr.outvars[grad_out_start + li]
            if hasattr(v, "count") and v in producer:
                r = max(r, producer[v] + 1)
        ready.append(r)
    cuts = sorted({r for r in ready if 0 < r < len(eqns)})
    return cuts, ready


# ---- the fused (one-program) ZeRO-1 step -----------------------------

class FusedZeroPrograms(NamedTuple):
    init: Any        # init(params) -> (params, opt) ZeRO-1 carry
    call: Any        # call(params, batch, opt) -> (loss, params, opt)
    call_final: Any  # call_final(params, loss_acc, acc, batch, opt)
    get: Any         # get(params, batch, opt, accumulate) -> the jit


def fused_zero_inner(loss_fn, params, batch, opt, hyper, layout,
                     treedef, axis, size, *, inter_axis=None,
                     inter_size=1, accumulate=False, loss_scale=1.0):
    """Build the flat per-rank fused grad+apply program and its example
    arguments: ``(inner, example_args, donate_argnums, axis_env)``.

    ``inner`` takes/returns FLAT leaves (so ``jax.make_jaxpr`` /
    ``_zero_spmd`` / ``jaxpr_as_fun`` compose without pytree plumbing):

        inputs  = (*params, [loss_acc, *acc,] *batch, *opt)
        outputs = (loss, *new_params, *new_opt)

    Body: ``value_and_grad(loss_fn)`` (+ the microbatch accumulator
    fold when ``accumulate``), bucket pack, then
    :func:`~horovod_tpu.parallel.zero.build_zero_apply_inner`'s
    per-bucket reduce-scatter -> (cross-plane psum) -> shard-adam ->
    all-gather pipeline, unpack. Traced with ``axis_env`` the
    collectives stay visible in the jaxpr — initially bunched after the
    backward, which is what :func:`interleave_collectives` then fixes.
    """
    from horovod_tpu.parallel.zero import build_zero_apply_inner

    p_leaves = treedef.flatten_up_to(params)
    b_leaves, btree = jax.tree.flatten(batch)
    opt_leaves, opt_tree = jax.tree.flatten(opt)
    n_p, n_b = len(p_leaves), len(b_leaves)
    apply_inner = build_zero_apply_inner(hyper, layout, axis, size,
                                         inter_axis=inter_axis,
                                         inter_size=inter_size)

    def scaled_loss(p, d):
        return (loss_fn(p, d) / loss_scale if loss_scale != 1.0
                else loss_fn(p, d))

    def inner(*flat):
        pos = 0
        p = jax.tree.unflatten(treedef, flat[pos:pos + n_p])
        pos += n_p
        if accumulate:
            loss_acc = flat[pos]
            acc = jax.tree.unflatten(treedef, flat[pos + 1:pos + 1 + n_p])
            pos += 1 + n_p
        d = jax.tree.unflatten(btree, flat[pos:pos + n_b])
        pos += n_b
        opt_state = jax.tree.unflatten(opt_tree, flat[pos:])
        loss, grads = jax.value_and_grad(scaled_loss)(p, d)
        if accumulate:
            loss = loss_acc + loss
            grads = jax.tree.map(jnp.add, acc, grads)
        g_flat = layout.pack(treedef.flatten_up_to(grads))
        p_flat = layout.pack(treedef.flatten_up_to(p))
        new_flat, new_opt = apply_inner(tuple(g_flat), tuple(p_flat),
                                        opt_state)
        new_leaves = layout.unpack(list(new_flat))
        return (loss, *new_leaves, *jax.tree.leaves(new_opt))

    example = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in p_leaves]
    if accumulate:
        example.append(jax.ShapeDtypeStruct((), jnp.float32))
        example.extend(jax.ShapeDtypeStruct(l.shape, l.dtype)
                       for l in p_leaves)
    example.extend(jax.ShapeDtypeStruct(l.shape, l.dtype)
                   for l in b_leaves)
    # The inner is a PER-MEMBER program (``_zero_spmd`` splits the opt
    # leaves over the axis before mapping): trace it with the 1/size
    # member shapes, exactly what each rank holds.
    example.extend(
        jax.ShapeDtypeStruct((l.shape[0] // size,) + tuple(l.shape[1:]),
                             l.dtype) for l in opt_leaves)
    # Donate params + opt leaves 1:1 into the new params/opt outputs;
    # batch (and the accumulator — the r6 lesson: grads never find an
    # output to alias once params claim theirs) stay un-donated.
    donate = tuple(range(n_p)) + tuple(
        range(len(example) - len(opt_leaves), len(example)))
    env = [(axis, size)]
    if inter_axis is not None:
        env.append((inter_axis, int(inter_size)))
    return inner, tuple(example), donate, env


def make_fused_zero_programs(loss_fn, optimizer, zero, *,
                             microbatches=1, jit_kwargs=None):
    """The jit-lane fused step programs for ``make_split_train_step``.

    Returns ``(init, call, call_final)``:

    - ``init(params) -> (params, opt)`` — identical carry to the
      unfused :func:`~horovod_tpu.parallel.zero.make_zero_apply` (the
      fusion knob can flip mid-run without converting state);
    - ``call(params, batch, opt) -> (loss, params, opt)`` — the fused
      single-microbatch step (grad + ZeRO apply, ONE program);
    - ``call_final(params, loss_acc, acc, batch, opt)`` — the fused
      LAST microbatch of an accumulation loop: earlier microbatches
      still run the plain grad programs (their collectives don't exist
      yet), only the step that owns the collective phase fuses.

    Each program is traced flat, rescheduled by
    :func:`interleave_collectives`, and run through ``_zero_spmd`` —
    ``jax.shard_map`` over ``zero.mesh``, a vmapped axis without one —
    with params/opt donated.
    """
    from horovod_tpu.parallel.zero import (
        _optimizer_hyper,
        _zero_spmd,
        zero_bucket_layout,
        zero_state_init,
    )

    hyper = _optimizer_hyper(optimizer)
    size = zero.resolved_size()
    jk = dict(jit_kwargs or {})
    n = int(microbatches)
    cache = {}

    def _programs(params, batch, opt, accumulate):
        p_leaves, treedef = jax.tree.flatten(params)
        key = (treedef, jax.tree.structure(batch), accumulate,
               tuple(tuple(l.shape) for l in jax.tree.leaves(batch)))
        if key in cache:
            return cache[key]
        layout = zero_bucket_layout(p_leaves, size, zero.bucket_bytes)
        inner, example, donate, env = fused_zero_inner(
            loss_fn, params, batch, opt, hyper, layout, treedef,
            zero.axis, size, inter_axis=zero.inter_axis,
            inter_size=zero.inter_size, accumulate=accumulate,
            loss_scale=float(n) if accumulate else 1.0)
        closed = jax.make_jaxpr(inner, axis_env=env)(*example)
        if jit_fusion_enabled():
            closed = interleave_collectives(closed)
        flat_fn = _jcore.jaxpr_as_fun(closed)
        n_p = len(p_leaves)
        n_opt = len(jax.tree.leaves(opt))
        split_in = tuple(i >= len(example) - n_opt
                         for i in range(len(example)))
        split_out = (False,) + (False,) * n_p + (True,) * n_opt
        spmd = _zero_spmd(lambda *a: tuple(flat_fn(*a)), zero.axis,
                          size, zero.mesh, split_in=split_in,
                          split_out=split_out,
                          inter_axis=zero.inter_axis,
                          inter_size=zero.inter_size)
        opt_tree = jax.tree.structure(opt)

        if accumulate:
            @functools.partial(jax.jit, donate_argnums=(0, 4), **jk)
            def call(params, loss_acc, acc, batch, opt):
                flat = (*treedef.flatten_up_to(params), loss_acc,
                        *treedef.flatten_up_to(acc),
                        *jax.tree.leaves(batch), *jax.tree.leaves(opt))
                outs = spmd(*flat)
                return (outs[0],
                        jax.tree.unflatten(treedef, outs[1:1 + n_p]),
                        jax.tree.unflatten(opt_tree, outs[1 + n_p:]))
        else:
            @functools.partial(jax.jit, donate_argnums=(0, 2), **jk)
            def call(params, batch, opt):
                flat = (*treedef.flatten_up_to(params),
                        *jax.tree.leaves(batch), *jax.tree.leaves(opt))
                outs = spmd(*flat)
                return (outs[0],
                        jax.tree.unflatten(treedef, outs[1:1 + n_p]),
                        jax.tree.unflatten(opt_tree, outs[1 + n_p:]))

        cache[key] = call
        return call

    def init(params):
        leaves, _ = jax.tree.flatten(params)
        layout = zero_bucket_layout(leaves, size, zero.bucket_bytes)
        return zero_state_init(hyper, layout, params, size)

    def call(params, batch, opt):
        return _programs(params, batch, opt, False)(params, batch, opt)

    def call_final(params, loss_acc, acc, batch, opt):
        return _programs(params, batch, opt, True)(
            params, loss_acc, acc, batch, opt)

    return FusedZeroPrograms(init=init, call=call,
                             call_final=call_final, get=_programs)
