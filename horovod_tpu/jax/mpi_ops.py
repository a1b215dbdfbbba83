"""JAX eager collective ops: hvd.allreduce & friends over jax arrays.

Reference analog: ``horovod/torch/mpi_ops.py`` (allreduce/allreduce_async/
synchronize/poll over framework tensors) — the reference has no JAX
frontend; this is the net-new ``horovod.jax`` from SURVEY.md §7 step 2.

Eager path: the jax array is brought to host, enqueued on the native core
(background negotiation + fused ring collectives over the control-plane
sockets), and the result re-wrapped as a jax array. For the in-graph
TPU-native path (psum over an ICI mesh inside jit), see
``horovod_tpu.parallel``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.common import eager_ops
from horovod_tpu.common.eager_ops import ReduceOp
from horovod_tpu.jax import xla_ici
from horovod_tpu.utils.spans import mark, span

# Reference-compatible reduce-op aliases (horovod/torch/mpi_ops.py).
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT
Adasum = ReduceOp.ADASUM

_basics = eager_ops._basics

# In elastic mode (HOROVOD_RDZV_ADDR set) init consults the driver's
# rendezvous for this epoch's rank assignment; static mode unchanged.
from horovod_tpu.common import elastic as _elastic_init_mod


def _maybe_enable_xla_data_plane():
    """HOROVOD_XLA_DATA_PLANE: 1 = require, 0 = off, auto (default) =
    enable when jax is on TPU (the configuration the backend exists
    for). On a TPU the plane coming up is part of ``init``: a failure
    raises — gradients quietly riding the host TCP ring beside an idle
    interconnect is a different system, not a degraded one. Choose the
    host ring there explicitly with ``HOROVOD_XLA_DATA_PLANE=0``."""
    flag = os.environ.get("HOROVOD_XLA_DATA_PLANE", "auto").lower()
    if flag in ("0", "false", "off"):
        return
    if flag in ("1", "true", "on"):
        xla_ici.enable()
        return
    # Is the backend a TPU? Asked WITHOUT touching it where possible:
    # in multi-process mode the backend must stay uninitialized until
    # jax.distributed.initialize (inside enable()). The environment
    # answers first; otherwise the chips on the PCI bus do — the same
    # probe jax itself uses to default to the TPU platform.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        on_tpu = "tpu" in platforms.split(",")
    else:
        from horovod_tpu.runner.util import local_tpu_chips

        on_tpu = local_tpu_chips() > 0 or (
            _basics.size() <= 1 and jax.default_backend() == "tpu")
    if on_tpu:
        xla_ici.enable()


def init(jit_fusion=None):
    """Initialize the runtime. ``jit_fusion`` (tri-state) overrides the
    ``HOROVOD_JIT_FUSION`` env knob for jit-lane compute/collective
    fusion (docs/fusion.md): ``False`` restores the unfused split-step
    schedule, ``True`` forces fusion on, ``None`` (default) follows the
    environment."""
    mark("hvd.init")
    if jit_fusion is not None:
        from horovod_tpu.parallel import fusion as _fusion

        _fusion.set_jit_fusion(jit_fusion)
    _elastic_init_mod.init()
    mark("hvd.init.core")
    _maybe_enable_xla_data_plane()   # hvd.init.plane: xla_ici's enable


# Elastic reset tears the data plane down with the old topology; try to
# bring it back up for the new epoch.
_elastic_init_mod.register_post_reset_hook(_maybe_enable_xla_data_plane)


def shutdown():
    # Flush any in-flight xprof trace first (users often skip
    # stop_timeline on teardown), then keep the device callback
    # registered until the background loop has drained (it may still be
    # executing device responses) before dropping it.
    _stop_xprof()
    _basics.shutdown()
    xla_ici.disable()


_xprof_active = False


def start_timeline(file_path, mark_cycles=False, xprof_dir=None):
    """Begin the runtime Chrome-trace timeline; optionally start a
    ``jax.profiler`` trace alongside so device-side XLA execution shows
    up in xprof/TensorBoard next to the negotiation timeline (reference
    analog: hvd.start_timeline + NVTX ranges for nsight;
    common/timeline.cc). ``xprof_dir`` defaults to
    ``HOROVOD_TIMELINE_XPROF`` when set.
    """
    global _xprof_active
    _basics.start_timeline(file_path, mark_cycles)
    xprof_dir = xprof_dir or os.environ.get("HOROVOD_TIMELINE_XPROF")
    if xprof_dir and not _xprof_active:
        jax.profiler.start_trace(str(xprof_dir))
        _xprof_active = True


def _stop_xprof():
    global _xprof_active
    if _xprof_active:
        jax.profiler.stop_trace()
        _xprof_active = False


def stop_timeline():
    _stop_xprof()
    _basics.stop_timeline()


def metrics():
    """Live snapshot of the native core's metrics registry, as a dict.

    Counter catalog in ``docs/metrics.md``: per-op-class counts/bytes
    (host ring and device plane), negotiation/queue/wire latency
    histograms, fusion-buffer fill, cycle stalls, response-cache hit
    rate, and the coordinator's per-rank straggler table. Counters are
    process-lifetime monotonic — diff snapshots to rate. For periodic
    export (JSONL flight recorder, Prometheus textfile, console) see
    ``horovod_tpu.telemetry.MetricsScraper``; for per-step MFU/goodput
    accounting see ``horovod_tpu.telemetry.StepTimer``.
    """
    return _basics.metrics_snapshot()


def metrics_reset():
    """Zero the metrics registry (tests / interactive use)."""
    core = sys.modules.get("horovod_tpu.telemetry.core")
    if core is not None:   # it also forgets who owns the open window
        core.metrics_reset()
    else:
        _basics.metrics_reset()


def events(last_n=0):
    """The newest ``last_n`` events of the core's structured event ring
    (``0`` = the whole live window), as a list of dicts — the always-on
    flight recorder behind black-box post-mortems (docs/metrics.md).

    Non-consuming: safe alongside the debug server's ``/events`` and
    the core's own fault dumps. Each event carries ``seq``, ``ts_us``
    (steady clock), ``type`` (``negotiate_begin``, ``response_launch``,
    ``wire_chunk``, ``retry_window``, ``fault``, ``knob_adopt``, ...)
    and per-type named args. For the cross-rank forensic merge see
    ``python -m horovod_tpu.telemetry.report --post-mortem``.
    """
    return _basics.events(last_n)


def debug_port():
    """The bound port of this rank's debug server, or ``None`` when it
    is not running — THE discovery path under ``HOROVOD_DEBUG_PORT=0``
    (ephemeral bind for co-located/simulated large worlds; the port is
    also echoed as the ``X-Hvdtpu-Debug-Port`` response header and in
    ``/healthz``). See docs/metrics.md / docs/scale.md."""
    from horovod_tpu.telemetry import debug_server

    return debug_server.debug_port()


def step_mark(begin=True):
    """Mark a training-step boundary for the step-anatomy layer
    (docs/metrics.md "Step anatomy"): ``step_begin``/``step_end``
    events scope every other flight-recorder event to a step window and
    the wire overlap ledger unions the wire spans inside it. Driven
    automatically by ``telemetry.StepTimer`` and
    ``hvd.DistributedFusedAdam``; call directly only when neither
    scopes your loop. Returns the step id."""
    return _basics.step_mark(begin)


is_initialized = _basics.is_initialized
rank = _basics.rank
size = _basics.size
local_rank = _basics.local_rank
local_size = _basics.local_size
cross_rank = _basics.cross_rank
cross_size = _basics.cross_size
is_homogeneous = _basics.is_homogeneous

for _cap in _basics.CAPABILITY_NAMES:
    globals()[_cap] = getattr(_basics, _cap)

from horovod_tpu.common.auto_name import make_auto_namer

_auto_name = make_auto_namer()



def _to_host(tensor):
    """jax/np array -> contiguous numpy view on host."""
    return np.asarray(tensor)


class Handle:
    """In-flight eager collective; ``synchronize`` returns a jax array."""

    def __init__(self, inner):
        self._inner = inner

    def poll(self):
        return self._inner.poll()

    def synchronize(self):
        out = self._inner.synchronize()
        return jnp.asarray(out)


def _device_path(tensor, op=None, process_set_id=0):
    """Route through the xla_ici data plane? Only for accelerator-resident
    jax arrays. Adasum runs on-device for power-of-two float groups (the
    recursive-doubling XLA program); otherwise it keeps the host path."""
    if not (xla_ici.active() and isinstance(tensor, jax.Array)):
        return False
    if op == Adasum:
        return xla_ici.adasum_device_supported(process_set_id,
                                               tensor.dtype)
    return True


def allreduce_async(tensor, name=None, op=Average, prescale_factor=1.0,
                    postscale_factor=1.0, process_set_id=0, donate=False):
    """``donate=True`` promises the input array will not be read again;
    on the device data plane the fused program then reuses its HBM for
    the result (the input is invalid afterwards). The host path ignores
    it (the host copy is already detached from the device buffer)."""
    if _device_path(tensor, op, process_set_id):
        return xla_ici.enqueue_device(
            "allreduce", tensor, name or _auto_name("allreduce"),
            reduce_op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set_id=process_set_id,
            donate=donate)
    arr = _to_host(tensor)
    inner = eager_ops.allreduce_async(
        arr, name or _auto_name("allreduce"), op=op,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        process_set_id=process_set_id)
    return Handle(inner)


def allreduce(tensor, name=None, op=Average, prescale_factor=1.0,
              postscale_factor=1.0, process_set_id=0):
    return allreduce_async(tensor, name, op, prescale_factor,
                           postscale_factor, process_set_id).synchronize()


def grouped_allreduce_async(tensors, names=None, op=Average,
                            prescale_factor=1.0, postscale_factor=1.0,
                            process_set_id=0, donate=False):
    """Allreduce a list of tensors as one negotiation group (they fuse and
    complete atomically). Reference analog: hvd.grouped_allreduce
    (horovod/common/group_table.cc). ``donate`` as in
    :func:`allreduce_async` (device plane only)."""
    with span("hvd.enqueue") as s:
        if s.is_enabled():
            s.set_metadata(tensors=len(tensors),
                           bytes=sum(getattr(t, "nbytes", 0)
                                     for t in tensors))
        return _enqueue_grouped_allreduce(
            tensors, names, op, prescale_factor, postscale_factor,
            process_set_id, donate)


def _enqueue_grouped_allreduce(tensors, names=None, op=Average,
                               prescale_factor=1.0, postscale_factor=1.0,
                               process_set_id=0, donate=False):
    """:func:`grouped_allreduce_async` without its ``hvd.enqueue`` span:
    for a caller that has opened the span itself round more of the work
    (``allreduce_gradients``: flatten and compress come first)."""
    if names is None:
        base = _auto_name("grouped_allreduce")
        names = [f"{base}.{i}" for i in range(len(tensors))]
    if (tensors and all(_device_path(t, op, process_set_id)
                        for t in tensors)
            and len({t.dtype for t in tensors}) == 1):
        return xla_ici.grouped_allreduce_device(
            tensors, names, reduce_op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set_id=process_set_id,
            donate=donate)
    arrs = [_to_host(t) for t in tensors]
    if arrs and all(a.dtype == arrs[0].dtype for a in arrs):
        inners = eager_ops.grouped_allreduce_async(
            arrs, names, op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set_id=process_set_id)
        return [Handle(i) for i in inners]
    # Mixed dtypes: fall back to per-tensor enqueue (still fuses per-dtype
    # in the core's fusion buffer, just not negotiated atomically).
    return [allreduce_async(t, n, op, prescale_factor, postscale_factor,
                            process_set_id, donate=donate)
            for t, n in zip(tensors, names)]


def grouped_allreduce(tensors, names=None, op=Average, prescale_factor=1.0,
                      postscale_factor=1.0, process_set_id=0):
    handles = grouped_allreduce_async(tensors, names, op, prescale_factor,
                                      postscale_factor, process_set_id)
    return [h.synchronize() for h in handles]


def allgather_async(tensor, name=None, process_set_id=0):
    if _device_path(tensor):
        return xla_ici.enqueue_device(
            "allgather", tensor, name or _auto_name("allgather"),
            process_set_id=process_set_id)
    arr = _to_host(tensor)
    inner = eager_ops.allgather_async(arr, name or _auto_name("allgather"),
                                      process_set_id=process_set_id)
    return Handle(inner)


def allgather(tensor, name=None, process_set_id=0):
    return allgather_async(tensor, name, process_set_id).synchronize()


def grouped_allgather_async(tensors, names=None, process_set_id=0):
    """Allgather a list of tensors as ONE negotiation group: atomic
    completion across ranks (reference analog: hvd.grouped_allgather;
    same group-promotion machinery as grouped allreduce — responses
    stay per-tensor, only allreduce buffer-fuses)."""
    if names is None:
        base = _auto_name("grouped_allgather")
        names = [f"{base}.{i}" for i in range(len(tensors))]
    if tensors and all(_device_path(t) for t in tensors):
        gid = (_basics.lib.hvdtpu_next_group_id()
               if len(tensors) > 1 else -1)
        return [xla_ici.enqueue_device(
                    "allgather", t, nm, process_set_id=process_set_id,
                    group_id=gid, group_size=len(tensors))
                for t, nm in zip(tensors, names)]
    arrs = [_to_host(t) for t in tensors]
    inners = eager_ops.grouped_allgather_async(
        arrs, list(names), process_set_id=process_set_id)
    return [Handle(i) for i in inners]


def grouped_allgather(tensors, names=None, process_set_id=0):
    handles = grouped_allgather_async(tensors, names, process_set_id)
    return [h.synchronize() for h in handles]


def broadcast_async(tensor, root_rank, name=None, process_set_id=0):
    if _device_path(tensor):
        return xla_ici.enqueue_device(
            "broadcast", tensor, name or _auto_name("broadcast"),
            root_rank=root_rank, process_set_id=process_set_id)
    arr = _to_host(tensor)
    inner = eager_ops.broadcast_async(arr, root_rank,
                                      name or _auto_name("broadcast"),
                                      process_set_id=process_set_id)
    return Handle(inner)


def broadcast(tensor, root_rank, name=None, process_set_id=0):
    return broadcast_async(tensor, root_rank, name,
                           process_set_id).synchronize()


def alltoall_async(tensor, splits=None, name=None, process_set_id=0):
    # Equal-split alltoall can run as ONE static XLA program — but only
    # the user knows every rank contributes the same shape (a rank can't
    # see its peers' shapes when routing, and the host ring legitimately
    # supports ragged splits=None). Opt in with HOROVOD_XLA_ALLTOALL=1;
    # mismatched shapes then fail loudly at negotiation.
    if (_device_path(tensor) and splits is None
            and os.environ.get("HOROVOD_XLA_ALLTOALL", "0").lower()
            in ("1", "true", "on")):
        n = xla_ici.alltoall_group_size(process_set_id)
        if n > 0 and tensor.ndim > 0 and tensor.shape[0] % n == 0:
            return xla_ici.enqueue_device(
                "alltoall", tensor, name or _auto_name("alltoall"),
                process_set_id=process_set_id)
    arr = _to_host(tensor)
    inner = eager_ops.alltoall_async(arr, splits,
                                     name or _auto_name("alltoall"),
                                     process_set_id=process_set_id)
    return Handle(inner)


def alltoall(tensor, splits=None, name=None, process_set_id=0):
    return alltoall_async(tensor, splits, name, process_set_id).synchronize()


def reducescatter_async(tensor, name=None, op=Average, prescale_factor=1.0,
                        postscale_factor=1.0, process_set_id=0):
    # Adasum reducescatter stays on the host path (the device program's
    # reducer has no per-shard adasum form).
    if op != Adasum and _device_path(tensor, op):
        return xla_ici.enqueue_device(
            "reducescatter", tensor, name or _auto_name("reducescatter"),
            reduce_op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set_id=process_set_id)
    arr = _to_host(tensor)
    inner = eager_ops.reducescatter_async(
        arr, name or _auto_name("reducescatter"), op=op,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        process_set_id=process_set_id)
    return Handle(inner)


def reducescatter(tensor, name=None, op=Average, prescale_factor=1.0,
                  postscale_factor=1.0, process_set_id=0):
    return reducescatter_async(tensor, name, op, prescale_factor,
                               postscale_factor, process_set_id).synchronize()


def grouped_reducescatter_async(tensors, names=None, op=Average,
                                process_set_id=0):
    """Reduce-scatter a list of tensors as ONE negotiation group
    (atomic completion; reference analog: hvd.grouped_reducescatter)."""
    if names is None:
        base = _auto_name("grouped_reducescatter")
        names = [f"{base}.{i}" for i in range(len(tensors))]
    if (tensors and op != Adasum
            and all(_device_path(t, op) for t in tensors)):
        gid = (_basics.lib.hvdtpu_next_group_id()
               if len(tensors) > 1 else -1)
        return [xla_ici.enqueue_device(
                    "reducescatter", t, nm, reduce_op=op,
                    process_set_id=process_set_id, group_id=gid,
                    group_size=len(tensors))
                for t, nm in zip(tensors, names)]
    arrs = [_to_host(t) for t in tensors]
    inners = eager_ops.grouped_reducescatter_async(
        arrs, list(names), op=op, process_set_id=process_set_id)
    return [Handle(i) for i in inners]


def grouped_reducescatter(tensors, names=None, op=Average,
                          process_set_id=0):
    handles = grouped_reducescatter_async(tensors, names, op,
                                          process_set_id)
    return [h.synchronize() for h in handles]


def synchronize(handle):
    return handle.synchronize()


def poll(handle):
    return handle.poll()


def barrier(process_set_id=0):
    eager_ops.barrier(process_set_id=process_set_id)


def join():
    """Block until every rank has joined; contribute zeros meanwhile.

    Reference analog: ``hvd.join`` (horovod/torch/mpi_ops.py).
    Returns the last rank to join.
    """
    return eager_ops.join()
