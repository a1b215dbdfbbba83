"""Static collective-byte predictors, built on the hvdlint jaxpr walker.

The lint world (``analysis/extract``) already knows how to turn any
traced program into its ordered collective signature; telemetry reuses
that walker as the *expected* side of the expected-vs-actual byte
reconciliation — one extractor, so the static analyzer and the runtime
counters can never disagree about what a program was supposed to move.

Two entry points:

- :func:`collective_bytes` — per-step bytes of any traceable SPMD
  program (psum/all_gather/... volumes, loops expanded by trip count).
- :func:`eager_allreduce_bytes` — the eager data-parallel step: one
  allreduce per gradient leaf. The gradient tree is traced as its
  in-graph equivalent (``psum`` of every ``grad`` leaf over a
  synthetic axis) and walked by the same extractor, so the predicted
  volume is literally the walker's sum over that signature.
"""

import numpy as np

from horovod_tpu.analysis.extract import extract, linearize


def _dtype_bytes(dtype_str):
    """Per-element bytes of a Collective's dtype tag. Mixed-dtype
    collectives join sorted names with commas; all repo collectives are
    homogeneous, so taking the first is exact today and a documented
    approximation otherwise."""
    name = dtype_str.split(",")[0] if dtype_str else "float32"
    try:
        return np.dtype(name).itemsize
    except TypeError:
        try:
            import ml_dtypes

            return np.dtype(getattr(ml_dtypes, name)).itemsize
        except (ImportError, AttributeError, TypeError):
            return 4


def signature_bytes(signature):
    """Sum payload bytes over a linearized collective signature."""
    return sum(c.nelems * _dtype_bytes(c.dtype)
               for c in linearize(signature))


def collective_bytes(fn, *args, axis_env=None):
    """Predicted per-call collective payload bytes of ``fn(*args)``.

    ``axis_env`` is a list of ``(axis_name, size)`` pairs binding the
    collective axes (same contract as ``analysis.lint``); args may be
    abstract (``jax.ShapeDtypeStruct``). Traced with ``jax.make_jaxpr``
    — no devices, mesh, or shard_map needed.
    """
    import jax

    jaxpr = jax.make_jaxpr(fn, axis_env=tuple(axis_env or ()))(*args)
    return signature_bytes(extract(jaxpr).signature)


def eager_allreduce_bytes(loss_fn, params, batch, size=2, axis="hvd"):
    """Predicted per-step wire bytes of the eager data-parallel step.

    The eager path allreduces every gradient leaf (grouped or not, the
    payload volume is the same); its in-graph equivalent is a ``psum``
    of each leaf over one axis, which is what gets traced and walked
    here. ``size`` only names the axis width for tracing — the
    per-rank payload volume (what the core's byte counters record on
    this rank) does not depend on it.
    """
    import jax

    def step_signature(p, b):
        grads = jax.grad(loss_fn)(p, b)
        return jax.tree.map(lambda g: jax.lax.psum(g, axis), grads)

    return collective_bytes(step_signature, params, batch,
                            axis_env=[(axis, size)])


def zero_signature_bytes(signature, size):
    """Sum payload bytes over a signature the way the RUNTIME counters
    account the ZeRO collective mix: reduce-scatter/psum_scatter at the
    full input width (the core books the enqueued tensor), all_gather
    at the GATHERED output width (the core books ``managed_output`` —
    ``size`` x the per-rank operand). One convention on both sides is
    what lets the reconciliation hold to <1% on the mixed
    reduce-scatter + allgather step (docs/zero.md)."""
    total = 0
    for c in linearize(signature):
        n = c.nelems * _dtype_bytes(c.dtype)
        if c.prim == "all_gather":
            n *= size
        total += n
    return total


def eager_zero_bytes(loss_fn, params, batch, size=2, axis="hvd",
                     bucket_bytes=None):
    """Predicted per-step wire payload bytes of the eager ZeRO-1 step
    (``hvd.DistributedFusedAdam(zero=True)``): one reduce-scatter per
    padded gradient bucket down, one allgather of the updated param
    shards per bucket up. The in-graph equivalent is built from the
    SAME ``parallel.zero.zero_bucket_layout`` the optimizer executes —
    padding included — and walked by the same extractor, so predicted
    and measured can only diverge if the runtime moves something the
    layout does not know about."""
    import jax

    from horovod_tpu.parallel.zero import (
        DEFAULT_BUCKET_BYTES,
        zero_bucket_layout,
    )

    bucket_bytes = bucket_bytes or DEFAULT_BUCKET_BYTES

    def step_signature(p, b):
        grads = jax.grad(loss_fn)(p, b)
        leaves, _ = jax.tree.flatten(grads)
        layout = zero_bucket_layout(leaves, size, bucket_bytes)
        out = []
        for flat in layout.pack(leaves):
            shard = jax.lax.psum_scatter(flat, axis,
                                         scatter_dimension=0, tiled=True)
            out.append(jax.lax.all_gather(shard, axis, axis=0,
                                          tiled=True))
        return out

    jaxpr = jax.make_jaxpr(step_signature,
                           axis_env=((axis, size),))(params, batch)
    return zero_signature_bytes(extract(jaxpr).signature, size)


def zero_layout_bytes(layout):
    """Walker-free cross-check for :func:`eager_zero_bytes`: per step,
    every padded bucket crosses once as a reduce-scatter input and once
    as a gathered allgather output — ``2 x padded x itemsize`` per
    bucket (the two must agree exactly; pinned in tests)."""
    return sum(2 * b.padded * b.dtype.itemsize for b in layout.buckets)


def hier_allreduce_wire_bytes(count, itemsize, size, local_size, rank,
                              compress_cross=False, compressed=False):
    """Per-rank, PER-PLANE transport tx bytes of one hierarchical
    cross-plane allreduce: ``{"intra": ..., "cross": ...}`` — the
    expected side of the core's split wire counters
    (``wire.cross_tx_bytes`` vs total; csrc/metrics.cc). Delegates to
    the reshard module so the predictor and the planner share ONE
    implementation of the ring segment math (exact reconciliation is
    pinned in ``make reshard-smoke``)."""
    from horovod_tpu.parallel.reshard import hier_wire_bytes

    return hier_wire_bytes(count, itemsize, size, local_size, rank,
                           compress_cross=compress_cross,
                           compressed=compressed)


def flat_ring_wire_bytes(count, itemsize, size, rank, compressed=False):
    """Per-rank transport tx bytes of one flat host-ring allreduce
    (the hierarchical predictor's baseline)."""
    from horovod_tpu.parallel.reshard import flat_allreduce_wire_bytes

    return flat_allreduce_wire_bytes(count, itemsize, size, rank,
                                     compressed=compressed)


def redistribute_bytes(plan, rank=None):
    """Predicted transport tx bytes of a :class:`ReshardPlan` (this
    rank, or the whole world) — what the reshard-smoke reconciles
    against the measured wire counters to < 1%."""
    return plan.wire_tx_bytes(rank)


def grad_tree_bytes(loss_fn, params, batch):
    """Gradient-tree byte volume via ``jax.eval_shape`` — the
    walker-free cross-check for :func:`eager_allreduce_bytes` (the two
    must agree exactly; the telemetry tests pin it)."""
    import jax

    shapes = jax.eval_shape(jax.grad(loss_fn), params, batch)
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(shapes))
