"""DistributedOptimizer for optax: allreduce-averaged gradients.

Reference analog: ``horovod/torch/optimizer.py`` ``_DistributedOptimizer``
(per-param async allreduce hooks + step-time synchronize) and
``horovod/tensorflow/gradient_aggregation.py`` (backward_passes_per_step
local aggregation). In optax terms this is a ``GradientTransformation``
that allreduces the incoming gradient pytree — grouped/fused in the native
core — before handing it to the wrapped transformation.
"""

import functools

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.jax import mpi_ops
from horovod_tpu.jax.compression import Compression
from horovod_tpu.utils.spans import span, step_begins, step_returns


# Step scoping from the eager optimizer (docs/metrics.md "Step
# anatomy"): each fused-optimizer apply() is a step BOUNDARY — the
# previous implicit window closes and the next opens, so window k spans
# "apply k returned" to "apply k+1 returned" = one full train step
# (grad compute + allreduce + update). Defers to an explicit scope: a
# StepTimer that opened a step the optimizer did not is driving the
# marks, and a second driver would fragment its windows. Deference is
# decided by the window OWNER, not the step id: core step ids restart
# after metrics_reset(), so an id-only comparison can mistake a
# StepTimer window that reused our last id for our own stale window
# and steal it mid-step (the overlap ledger then folds one step's wire
# spans into two half-windows and the attribution is garbage).
_last_boundary_id = None


def _mark_optimizer_step():
    global _last_boundary_id
    try:
        from horovod_tpu.telemetry import core as _tcore

        if _tcore.window_owner() not in (None, "optimizer"):
            return  # an explicit scope (StepTimer) owns the window
        open_id = _tcore.step_id()
        if open_id >= 0 and open_id != _last_boundary_id:
            return  # an undeclared driver opened it — leave it alone
        _last_boundary_id = _tcore.step_mark(True, owner="optimizer")
    except Exception:  # noqa: BLE001 — telemetry must never take the
        pass           # training step down


def allreduce_gradients(grads, op=mpi_ops.Average,
                        compression=Compression.none, prefix="grad",
                        donate=False):
    """Allreduce a gradient pytree across ranks (eager path).

    Leaves are enqueued as one negotiation group per dtype so the core
    fuses them into large buffers (reference: tensor fusion,
    HOROVOD_FUSION_THRESHOLD).

    ``donate=True`` promises the caller will not read ``grads`` again
    (the usual case — the reduced tree replaces them): on the device
    data plane the fused program reuses the gradients' HBM for the
    results, halving the collective's peak footprint.
    """
    # In the eager lane this call is where the library sees a step begin
    # and return (the compile log's ``at_step``): the user's own grad
    # program runs before it, their apply after it.
    step_begins()
    try:
        # The user's thread in two spans (docs/metrics.md "Program spans"):
        # hvd.enqueue hands every leaf to the core, hvd.wait sleeps until
        # the device plane has stored the last result. Between them, on the
        # core's thread, lie its cycle and hvd.device_exec.
        with span("hvd.enqueue") as s:
            leaves, treedef = jax.tree.flatten(grads)
            del grads  # with donate, no live ref may outlast the collective
            compressed, ctxs = [], []
            for leaf in leaves:
                c, ctx = compression.compress(jnp.asarray(leaf))
                compressed.append(c)
                ctxs.append(ctx)
            del leaves
            if s.is_enabled():
                s.set_metadata(tensors=len(compressed),
                               bytes=sum(c.nbytes for c in compressed))
            names = [f"{prefix}.{i}" for i in range(len(compressed))]
            handles = mpi_ops._enqueue_grouped_allreduce(
                compressed, names, op=op, donate=donate)
            del compressed
        with span("hvd.wait"):
            results = [h.synchronize() for h in handles]
        reduced = [compression.decompress(r, ctx)
                   for r, ctx in zip(results, ctxs)]
        return jax.tree.unflatten(treedef, reduced)
    finally:
        step_returns()


def DistributedGradientTransformation(optimizer, op=mpi_ops.Average,
                                      compression=Compression.none,
                                      backward_passes_per_step=1):
    """Wrap an optax GradientTransformation so update() sees gradients
    allreduce-averaged across all ranks.

    With ``backward_passes_per_step > 1`` gradients are accumulated
    locally and only allreduced (and applied) every Nth call — the
    reference's LocalGradientAggregationHelper. Between allreduce steps
    the update is zero (parameters unchanged), matching the reference's
    semantics of skipping apply.
    """
    if backward_passes_per_step == 1:
        def update(grads, state, params=None):
            reduced = allreduce_gradients(grads, op=op,
                                          compression=compression)
            return optimizer.update(reduced, state, params)

        return optax.GradientTransformation(optimizer.init, update)

    def init(params):
        return {
            "inner": optimizer.init(params),
            "acc": jax.tree.map(jnp.zeros_like, params),
            "counter": 0,
        }

    def update(grads, state, params=None):
        acc = jax.tree.map(lambda a, g: a + g, state["acc"], grads)
        counter = state["counter"] + 1
        if counter < backward_passes_per_step:
            zero = jax.tree.map(jnp.zeros_like, grads)
            return zero, {"inner": state["inner"], "acc": acc,
                          "counter": counter}
        scale = 1.0 / backward_passes_per_step
        acc = jax.tree.map(lambda a: a * scale, acc)
        reduced = allreduce_gradients(acc, op=op, compression=compression)
        updates, inner = optimizer.update(reduced, state["inner"], params)
        return updates, {"inner": inner,
                         "acc": jax.tree.map(jnp.zeros_like, acc),
                         "counter": 0}

    return optax.GradientTransformation(init, update)


# Reference-familiar name.
DistributedOptimizer = DistributedGradientTransformation


def DistributedFusedAdam(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
                         op=mpi_ops.Average,
                         compression=Compression.none,
                         zero=False, bucket_bytes=None, overlap=True):
    """Eager-Horovod counterpart of the single-pass fused update
    (``parallel.precision.fused_adam``): allreduce the gradient pytree
    across ranks (donated — the fused device program reuses the
    gradients' HBM), then apply adam in ONE jitted pass over params
    (no updates tree, no separate ``optax.apply_updates`` pass over
    param-sized arrays).

    Protocol matches ``FusedOptimizer`` (``init(params) -> state``,
    ``apply(params, grads, state) -> (params, state)``) for use in an
    eager step loop::

        opt = hvd.DistributedFusedAdam(3e-4)
        state = opt.init(params)
        loss, grads = grad_fn(params, batch)        # jitted fwd+bwd
        params, state = opt.apply(params, grads, state)

    The allreduce is an eager collective (enqueue -> negotiate ->
    cached device-program replay), so ``apply`` itself must stay
    OUTSIDE jit; the update math runs as its own jitted program — the
    same split-program layout ``chip_smoke.py``'s eager step runs.

    ``zero=True`` switches to the ZeRO-1 sharded path (docs/zero.md):
    gradients are packed into fused buckets (``bucket_bytes``,
    shard-aligned by construction — ``parallel.zero``) and
    **reduce-scattered** instead of allreduced, each rank runs the
    identical adam kernel on its 1/N (params, mu, nu) shards, and the
    updated param shards are **allgathered** back. Per-rank optimizer
    state drops N-fold. With ``overlap=True`` (default) the lane is
    pipelined per bucket: every reduce-scatter is in flight before the
    first shard update runs, and each bucket's allgather is issued the
    moment its update finishes — wire time hides under the remaining
    buckets' update compute (the fused computation-collective recipe of
    arXiv:2305.06942); ``overlap=False`` runs the three phases
    bulk-synchronously (the ``zero_sweep`` comparison point). In zero
    mode ``compression`` applies to the param-allgather payload (e.g.
    ``Compression.bf16`` halves the up-phase wire for fp32 params;
    every rank — shard owners included — consumes the decompressed
    bits, so the result stays rank-consistent), and the gradient
    reduce-scatter rides the core's ``HOROVOD_WIRE_COMPRESSION``
    bf16-on-wire path.
    """
    from horovod_tpu.parallel.precision import FusedOptimizer, fused_adam

    if zero:
        zopt = _zero_fused_adam(learning_rate, b1, b2, eps, op=op,
                                compression=compression,
                                bucket_bytes=bucket_bytes,
                                overlap=overlap)
        return FusedOptimizer(init=zopt.init,
                              apply=_boundary_marked(zopt.apply),
                              hyper=zopt.hyper)

    inner = fused_adam(learning_rate, b1=b1, b2=b2, eps=eps)

    # Grads are NOT donated into the update jit: they arrive as
    # donation-aliased outputs of the device-plane program and XLA
    # refuses to re-donate an aliased buffer (see chip_smoke.py's
    # apply_fn). params/state donation is what bounds the peak.
    jitted_apply = jax.jit(inner.apply, donate_argnums=(0, 2))

    def apply(params, grads, state):
        grads = allreduce_gradients(grads, op=op,
                                    compression=compression,
                                    donate=True)
        return jitted_apply(params, grads, state)

    return FusedOptimizer(init=inner.init,
                          apply=_boundary_marked(apply),
                          hyper=inner.hyper)


def _boundary_marked(apply_fn):
    """Wrap an optimizer apply so every completed update marks a step
    boundary (see :func:`_mark_optimizer_step`)."""
    @functools.wraps(apply_fn)
    def apply(params, grads, state):
        out = apply_fn(params, grads, state)
        _mark_optimizer_step()
        return out

    return apply


def _zero_fused_adam(learning_rate, b1, b2, eps, op, compression,
                     bucket_bytes, overlap):
    """The eager ZeRO-1 lane behind ``DistributedFusedAdam(zero=True)``.

    One negotiation name per bucket per phase (``zero.rs.i`` /
    ``zero.ag.i``) so the steady-state response cache stays hot. The
    pipelined order is: issue EVERY bucket's reduce-scatter first (the
    background thread negotiates and executes them while Python works),
    then walk the buckets in order — synchronize bucket i's shard,
    run its jitted shard-adam, fire its allgather, move on — so bucket
    i's allgather and bucket i+1..K's reduce-scatters overlap bucket
    i+1's update compute. Synchronizing the allgathers last drains the
    pipe.
    """
    from horovod_tpu.parallel.precision import (
        FusedOptimizer,
        _adam_leaf,
        _bias_corrections,
    )
    from horovod_tpu.parallel.zero import (
        DEFAULT_BUCKET_BYTES,
        zero_bucket_layout,
    )

    bucket_bytes = bucket_bytes or DEFAULT_BUCKET_BYTES
    cache = {}  # treedef -> layout

    def _layout(leaves, treedef):
        if treedef not in cache:
            cache[treedef] = zero_bucket_layout(
                leaves, mpi_ops.size(), bucket_bytes)
        return cache[treedef]

    # mu/nu are donated (replaced every step); p/g shards arrive as
    # fresh collective outputs or slices and must stay un-donated.
    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def shard_adam(p_shard, g_shard, mu, nu, count):
        bc1, bc2 = _bias_corrections(count, b1, b2)
        return _adam_leaf(p_shard, g_shard, mu, nu, learning_rate, b1,
                          b2, eps, bc1, bc2, p_shard.dtype)

    def init(params):
        leaves, treedef = jax.tree.flatten(params)
        layout = _layout(leaves, treedef)
        n = layout.n_shards
        shard = lambda b: jnp.zeros(  # noqa: E731
            (b.shard_elems(n),), b.dtype)
        return {
            "count": jnp.zeros((), jnp.int32),
            "mu": [shard(b) for b in layout.buckets],
            "nu": [shard(b) for b in layout.buckets],
        }

    def apply(params, grads, state):
        rank = mpi_ops.rank()
        g_leaves, treedef = jax.tree.flatten(grads)
        del grads
        layout = _layout(g_leaves, treedef)
        p_leaves = treedef.flatten_up_to(params)
        count = state["count"] + 1
        # Phase down: EVERY bucket's reduce-scatter goes in flight
        # before any update runs (overlap) / is drained immediately
        # (phase-separated baseline).
        rs = []
        for i, flat in enumerate(layout.pack(g_leaves)):
            h = mpi_ops.reducescatter_async(flat, name=f"zero.rs.{i}",
                                            op=op)
            rs.append(h if overlap else h.synchronize())
        del g_leaves
        # Update + phase up, pipelined per bucket. The param shard is
        # assembled directly from the overlapping leaf slices
        # (layout.pack_shard) — packing the FULL padded bucket only to
        # slice out 1/N of it would waste (N-1)/N of the copy on the
        # hot eager path.
        ag, ctxs, new_mu, new_nu = [], [], [], []
        for i in range(len(layout.buckets)):
            g_shard = rs[i].synchronize() if overlap else rs[i]
            p_shard = layout.pack_shard(p_leaves, i, rank)
            p2, mu2, nu2 = shard_adam(p_shard, g_shard, state["mu"][i],
                                      state["nu"][i], count)
            new_mu.append(mu2)
            new_nu.append(nu2)
            c, ctx = compression.compress(p2)
            ctxs.append(ctx)
            if overlap:
                ag.append(mpi_ops.allgather_async(c, name=f"zero.ag.{i}"))
            else:
                ag.append(c)
        if not overlap:
            ag = mpi_ops.grouped_allgather_async(
                ag, names=[f"zero.ag.{i}" for i in range(len(ag))])
        new_flat = [compression.decompress(h.synchronize(), ctx)
                    for h, ctx in zip(ag, ctxs)]
        params = jax.tree.unflatten(treedef, layout.unpack(new_flat))
        return params, {"count": count, "mu": new_mu, "nu": new_nu}

    return FusedOptimizer(init=init, apply=apply,
                          hyper={"kind": "adam", "zero1": True,
                                 "learning_rate": learning_rate,
                                 "b1": b1, "b2": b2, "eps": eps})


def make_fused_train_step(loss_fn, learning_rate, b1=0.9, b2=0.999,
                          eps=1e-8, op=mpi_ops.Average,
                          compression=Compression.none,
                          bucket_bytes=None):
    """The host-lane fused ZeRO-1 train step: per-bucket reduce-scatter
    interleaved with the jitted backward (docs/fusion.md).

    The backward is traced once and SPLIT at bucket-readiness
    boundaries (``parallel.fusion.grad_bucket_cuts`` /
    ``segment_closed_jaxpr``): the step loop runs the compute segments
    back-to-back and, at each boundary, fires the eager reduce-scatter
    for every gradient bucket that segment completed — so the wire
    drains bucket k while segments k+1.. are still computing, exactly
    the eager lane's overlap recipe applied to a jitted backward. Each
    bucket's shard-adam and param allgather then pipeline as in
    ``DistributedFusedAdam(zero=True)``, but the allgathers'
    SYNCHRONIZATION is deferred into the NEXT step: ``step`` returns
    with the gathers still in flight (carried as ``pending``), and the
    next call drains them right before the forward needs the updated
    params — the up-phase wire overlaps the inter-step host work and
    shows up as hidden time in the next step's overlap window.

    ``HOROVOD_JIT_FUSION=0`` (or ``hvd.init(jit_fusion=False)``)
    switches the SAME step to the unfused schedule — monolithic grad
    program, bulk-synchronous reduce-scatter / update / allgather
    phases, params materialized before ``step`` returns. Both lanes run
    identical collectives with identical operands in the same per-axis
    order, so the knob changes the schedule, never the math: loss
    trajectories are bit-identical (tests/parallel/test_fusion.py).

    Returns ``(init, step, finish)``::

        init(params)          -> carry
        step(carry, batch)    -> (loss, carry)     # params may lag one
        finish(carry)         -> (params, carry)   # drain in-flight AG

    ``finish`` must be called before reading params (checkpoint, eval)
    in the fused schedule; it is a no-op when nothing is pending.
    """
    from horovod_tpu.parallel import fusion
    from horovod_tpu.parallel.precision import (
        _adam_leaf,
        _bias_corrections,
    )
    from horovod_tpu.parallel.zero import (
        DEFAULT_BUCKET_BYTES,
        zero_bucket_layout,
    )

    bucket_bytes = bucket_bytes or DEFAULT_BUCKET_BYTES
    progs = {}  # (treedef, batch structure) -> traced/segmented lane

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def shard_adam(p_shard, g_shard, mu, nu, count):
        bc1, bc2 = _bias_corrections(count, b1, b2)
        return _adam_leaf(p_shard, g_shard, mu, nu, learning_rate, b1,
                          b2, eps, bc1, bc2, p_shard.dtype)

    def _lane(p_leaves, treedef, b_leaves, btree):
        key = (treedef, btree,
               tuple((l.shape, jnp.dtype(l.dtype).name)
                     for l in (*p_leaves, *b_leaves)))
        if key in progs:
            return progs[key]
        layout = zero_bucket_layout(p_leaves, mpi_ops.size(),
                                    bucket_bytes)
        n_p = len(p_leaves)

        def flat_grad(*flat):
            p = jax.tree.unflatten(treedef, flat[:n_p])
            d = jax.tree.unflatten(btree, flat[n_p:])
            loss, grads = jax.value_and_grad(loss_fn)(p, d)
            return (loss, *treedef.flatten_up_to(grads))

        closed = jax.make_jaxpr(flat_grad)(*p_leaves, *b_leaves)
        cuts, ready = fusion.grad_bucket_cuts(closed, layout)
        prog = fusion.segment_closed_jaxpr(closed, cuts)
        # boundary k fires after segment k (prefix length bounds[k+1]):
        # bucket b joins the FIRST boundary whose prefix covers its
        # last producing equation.
        bounds = [0, *cuts, len(closed.jaxpr.eqns)]
        at_boundary = [[] for _ in range(len(bounds) - 1)]
        for bi, r in enumerate(ready):
            k = next(k for k in range(len(bounds) - 1)
                     if bounds[k + 1] >= r)
            at_boundary[k].append(bi)
        issue_order = sorted(range(len(layout.buckets)),
                             key=ready.__getitem__)
        grad_vars = closed.jaxpr.outvars[1:]
        # One packer jit per bucket: same dynamic_update_slice chain as
        # BucketLayout.pack, over just that bucket's leaves — shared by
        # both schedules so the wire sees identical operands.
        packers = []
        for b in layout.buckets:
            def pack(*leaves, _b=b):
                flat = jnp.zeros((_b.padded,), _b.dtype)
                for leaf, off in zip(leaves, _b.offsets):
                    flat = jax.lax.dynamic_update_slice(
                        flat, leaf.reshape(-1).astype(_b.dtype), (off,))
                return flat
            packers.append(jax.jit(pack))
        monolithic = jax.jit(flat_grad)
        lane = (layout, prog, at_boundary, issue_order, grad_vars,
                packers, monolithic)
        progs[key] = lane
        return lane

    def init(params):
        leaves, _ = jax.tree.flatten(params)
        layout = zero_bucket_layout(leaves, mpi_ops.size(),
                                    bucket_bytes)
        n = layout.n_shards
        shard = lambda b: jnp.zeros(  # noqa: E731
            (b.shard_elems(n),), b.dtype)
        state = {"count": jnp.zeros((), jnp.int32),
                 "mu": [shard(b) for b in layout.buckets],
                 "nu": [shard(b) for b in layout.buckets]}
        return (params, state, None)

    def _drain(params, pending):
        """Resolve the previous step's in-flight allgathers into the
        updated params (no-op when nothing is pending)."""
        if pending is None:
            return params
        handles, ctxs, layout, treedef = pending
        new_flat = [compression.decompress(h.synchronize(), ctx)
                    for h, ctx in zip(handles, ctxs)]
        return jax.tree.unflatten(treedef, layout.unpack(new_flat))

    def _leaf_val(env, v):
        return v.val if isinstance(v, fusion._jcore.Literal) else env[v]

    def step(carry, batch):
        params, state, pending = carry
        params = _drain(params, pending)
        fused = fusion.jit_fusion_enabled()
        rank = mpi_ops.rank()
        p_leaves, treedef = jax.tree.flatten(params)
        b_leaves, btree = jax.tree.flatten(batch)
        (layout, prog, at_boundary, issue_order, grad_vars, packers,
         monolithic) = _lane(p_leaves, treedef, b_leaves, btree)
        count = state["count"] + 1
        rs = {}
        if fused:
            def on_boundary(k, env):
                # Fire the reduce-scatter of every bucket this segment
                # finished; the remaining segments compute over it.
                for bi in at_boundary[k]:
                    b = layout.buckets[bi]
                    flat = packers[bi](*(
                        _leaf_val(env, grad_vars[li]) for li in b.indices))
                    rs[bi] = mpi_ops.reducescatter_async(
                        flat, name=f"fusion.rs.{bi}", op=op)
            outs, _ = prog.run(*p_leaves, *b_leaves,
                               on_boundary=on_boundary)
            loss = outs[0]
        else:
            outs = monolithic(*p_leaves, *b_leaves)
            loss, g_leaves = outs[0], list(outs[1:])
            # Unfused: bulk-synchronous phase — every scatter drained
            # before any update runs (the pre-fusion split schedule).
            for bi, b in enumerate(layout.buckets):
                flat = packers[bi](*(g_leaves[li] for li in b.indices))
                rs[bi] = mpi_ops.reducescatter_async(
                    flat, name=f"fusion.rs.{bi}", op=op)
            rs = {bi: h.synchronize() for bi, h in rs.items()}
        new_mu = list(state["mu"])
        new_nu = list(state["nu"])
        ag, ctxs = [None] * len(layout.buckets), [None] * len(
            layout.buckets)
        for bi in issue_order:
            g_shard = rs[bi].synchronize() if fused else rs[bi]
            p_shard = layout.pack_shard(p_leaves, bi, rank)
            p2, mu2, nu2 = shard_adam(p_shard, g_shard, new_mu[bi],
                                      new_nu[bi], count)
            new_mu[bi], new_nu[bi] = mu2, nu2
            c, ctx = compression.compress(p2)
            ctxs[bi] = ctx
            ag[bi] = mpi_ops.allgather_async(c, name=f"fusion.ag.{bi}")
        state = {"count": count, "mu": new_mu, "nu": new_nu}
        pending = (ag, ctxs, layout, treedef)
        if not fused:
            # Unfused: params materialize before the step returns.
            params = _drain(params, pending)
            pending = None
        _mark_optimizer_step()
        return loss, (params, state, pending)

    def finish(carry):
        """Drain any in-flight allgathers; returns
        ``(params, carry)`` with the carry safe to keep stepping."""
        params, state, pending = carry
        params = _drain(params, pending)
        return params, (params, state, None)

    return init, step, finish
