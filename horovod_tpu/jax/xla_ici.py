"""xla_ici device data plane: eager collectives as cached XLA programs.

Reference analog: the NCCL data-plane backend
(``horovod/common/ops/nccl_operations.cc``) plus the fusion buffer
(``horovod/common/fusion_buffer_manager.cc``) — re-founded on XLA per
SURVEY.md §7's key insight: Horovod's response cache ≅ a compiled-
executable cache. Each fused group of device tensors becomes ONE jitted
program — one variadic ``psum`` over the mesh axis, every tensor in its
own shape, with pre/postscale folded in — compiled once per (op, shapes,
dtype, scales, process-set) signature and replayed every later step. The
C++ core keeps what it's good at: negotiation, ordering, fusion grouping, the response
cache, and join handling over the host network. Because every member rank
receives the identical fused ResponseList, the per-rank program launches
line up into one collective over ICI (TPU pods) or the gloo CPU backend
(tests).

Topology: one device per rank ("rank-per-chip"). Multi-process runs
require ``jax.distributed`` to be initialized with one process per rank;
``enable()`` does this itself from the controller address when possible,
and learns which jax process is which rank by asking the ranks.
"""

import ctypes
import os
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common import eager_ops, process_sets
from horovod_tpu.common.basics import HorovodBasics
from horovod_tpu.common.eager_ops import _DTYPE_TO_ENUM, ReduceOp
from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.utils.spans import mark, register_program, scope, span

_basics = HorovodBasics()

_ENUM_TO_DTYPE = {v: k for k, v in _DTYPE_TO_ENUM.items()}

# Response::ResponseType values (csrc/message.h) — the callback's op_class.
_OP_ALLREDUCE = 0
_OP_ALLGATHER = 1
_OP_BROADCAST = 2
_OP_ALLTOALL = 3
_OP_REDUCESCATTER = 4

_OP_NAMES = {_OP_ALLREDUCE: "allreduce", _OP_ALLGATHER: "allgather",
             _OP_BROADCAST: "broadcast", _OP_ALLTOALL: "alltoall",
             _OP_REDUCESCATTER: "reducescatter"}

_EXEC_FN = ctypes.CFUNCTYPE(
    ctypes.c_int32,                    # return: 0 ok, nonzero = error
    ctypes.c_int32,                    # op_class
    ctypes.c_int32,                    # n fused tensors
    ctypes.POINTER(ctypes.c_char_p),   # names
    ctypes.POINTER(ctypes.c_int64),    # shapes_flat [ndim, dims...]*n
    ctypes.c_int32,                    # dtype enum
    ctypes.c_int32,                    # reduce_op
    ctypes.c_int32,                    # root_rank
    ctypes.c_int32,                    # process_set_id
    ctypes.POINTER(ctypes.c_int64),    # rank_sizes (allgather first dims)
    ctypes.c_int32,                    # n_rank_sizes
    ctypes.POINTER(ctypes.c_char),     # err buffer
    ctypes.c_int32)                    # err capacity


def _decode_shapes(shapes_p, n):
    shapes, pos = [], 0
    for _ in range(n):
        ndim = int(shapes_p[pos])
        pos += 1
        shapes.append(tuple(int(shapes_p[pos + j]) for j in range(ndim)))
        pos += ndim
    return shapes


def _nelem(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _row_elems(rest):
    """Elements per first-dim row: 1 for scalar rows (rest == ()), the
    true product otherwise — including 0 for zero-size trailing dims
    (``x or 1`` would corrupt those)."""
    return _nelem(rest) if rest else 1


def _distributed_initialized():
    """Whether jax.distributed.initialize already ran — checked WITHOUT
    touching the backend (jax.process_count() would initialize it, locking
    in a single-process topology)."""
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception:  # pragma: no cover - private API moved
        return False


class XlaIciDataPlane:
    """Executes the core's fused device responses as cached XLA programs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active = False
        self._rank = 0
        self._size = 1
        self._devices = None          # rank -> jax device
        self._local_device = None
        self._inputs = {}             # (ps_id, name) -> (array, pre, post,
                                      #                   donate)
        self._outputs = {}            # (ps_id, name) -> jax array
        self._exec_cache = {}         # signature -> jitted program
        self._cb_ref = None           # keep the CFUNCTYPE alive
        self._retained_topology = None  # topology the cache compiled for
        self.cache_reuses = 0         # enables that kept the cache
        self.cache_invalidations = 0  # enables that had to clear it

    # -- lifecycle ---------------------------------------------------------

    @property
    def active(self):
        return self._active

    def enable(self):
        """Bind one device per rank and register with the core.

        Multi-process: initializes ``jax.distributed`` against the
        controller host (port = HOROVOD_XLA_COORD_PORT or controller
        port + 1) unless the caller already did.

        Plane selection (``HOROVOD_CROSS_PLANE``, docs/redistribute.md):
        ``ring`` forces every collective onto the host ring — the
        device plane refuses to activate so frontends transparently
        fall back; ``ici``/``auto``/``hier`` all want this plane up
        (under ``hier`` the HOST side of a device-ineligible collective
        still decomposes hierarchically in the core).
        """
        if self._active:
            return
        if cross_plane_mode() == "ring":
            raise RuntimeError(
                "HOROVOD_CROSS_PLANE=ring forces host-ring collectives; "
                "the xla_ici device plane stays disabled under it")
        rank, size = _basics.rank(), _basics.size()
        if rank < 0:
            raise RuntimeError("hvd.init() must run before the XLA data "
                               "plane is enabled")
        if size > 1:
            if not _distributed_initialized():
                addr = os.environ.get("HOROVOD_CONTROLLER_ADDR", "127.0.0.1")
                port = int(os.environ.get(
                    "HOROVOD_XLA_COORD_PORT",
                    int(os.environ.get("HOROVOD_CONTROLLER_PORT", 29500)) + 1))
                # Must run BEFORE the backend client exists (so don't probe
                # jax.default_backend() here). The CPU collectives setting
                # is inert on TPU.
                try:
                    jax.config.update("jax_cpu_collectives_implementation",
                                      "gloo")
                except Exception:  # backend already up; keep its setting
                    pass
                jax.distributed.initialize(
                    coordinator_address=f"{addr}:{port}",
                    num_processes=size, process_id=rank)
            if jax.process_count() != size:
                raise RuntimeError(
                    f"jax.distributed sees {jax.process_count()} "
                    f"processes, Horovod {size} ranks")
            # Which jax process is which rank? Not the id handed to
            # jax.distributed: a TPU runtime numbers the processes of a
            # host by its own rules (four --tpu-pod ranks on a v5e 2x2
            # came up as processes 1, 3, 2, 0). The ranks tell each
            # other, over the host ring that is already up.
            procs = eager_ops.allgather_async(
                np.array([jax.process_index()], np.int32),
                "xla_ici.process_index").synchronize()
            by_proc = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, []).append(d)
            self._devices = []
            for r, p in enumerate(int(p) for p in procs):
                devs = by_proc.get(p)
                if not devs:
                    raise RuntimeError(f"no jax device for process {p} "
                                       f"(rank {r})")
                # Rank-per-chip: one device per process. If a process owns
                # several (e.g. CPU tests), every rank still uses its first
                # so device lists agree across ranks.
                self._devices.append(devs[0])
            self._local_device = self._devices[rank]
        else:
            self._local_device = jax.local_devices()[0]
            self._devices = [self._local_device]
        self._rank, self._size = rank, size
        # Elastic fast re-init (SURVEY §7 hard part: "recovery requires
        # tearing down and re-creating the PJRT client/mesh; slow — needs
        # a cached-topology fast path"): compiled executables stay valid
        # as long as (rank, size, device list) — everything their meshes
        # and shard layouts close over — is unchanged. The common
        # recovery case (a worker replaced at the same size) re-enables
        # with the identical topology on every surviving rank, so the
        # whole executable cache replays instead of recompiling. Any
        # topology drift invalidates the lot.
        topology = (rank, size, tuple(self._devices))
        if self._exec_cache:
            if topology == self._retained_topology:
                self.cache_reuses += 1
            else:
                self._exec_cache.clear()
                self.cache_invalidations += 1
        self._retained_topology = topology
        self._cb_ref = _EXEC_FN(self._execute)
        _basics.lib.hvdtpu_set_device_callback(
            ctypes.cast(self._cb_ref, ctypes.c_void_p))
        self._active = True
        mark("hvd.init.plane")

    def disable(self):
        if not self._active:
            return
        _basics.lib.hvdtpu_set_device_callback(None)
        self._active = False
        self._cb_ref = None
        # In-flight payloads die with the epoch; the executable cache is
        # RETAINED against self._retained_topology — enable() decides
        # whether the next epoch can reuse it (elastic fast re-init) or
        # must recompile (topology changed).
        with self._lock:
            self._inputs.clear()
            self._outputs.clear()

    def executable_cache_size(self):
        return len(self._exec_cache)

    def invalidate(self):
        """Drop every retained executable NOW (not at the next enable).

        The fast re-init retention assumes the jax backend client
        persists across the disable/enable cycle — true for in-process
        elastic recovery, where jax.distributed cannot re-initialize a
        different world anyway. Anything that genuinely tears down and
        recreates the PJRT client must call this first: retained
        executables pin the OLD client's devices until enable() sees
        the topology changed."""
        self._exec_cache.clear()
        self._retained_topology = None

    # -- frontend side -----------------------------------------------------

    def register_input(self, name, process_set_id, array, prescale=1.0,
                       postscale=1.0, donate=False):
        arr = jax.device_put(array, self._local_device)
        with self._lock:
            self._inputs[(process_set_id, name)] = (arr, float(prescale),
                                                    float(postscale),
                                                    bool(donate))
        return arr

    def pop_output(self, name, process_set_id):
        with self._lock:
            return self._outputs.pop((process_set_id, name))

    def drop(self, name, process_set_id):
        """Release any buffers pinned for a failed collective (ERROR
        response or enqueue failure — the callback never ran, so nothing
        else pops the input and the HBM would stay pinned)."""
        with self._lock:
            self._inputs.pop((process_set_id, name), None)
            self._outputs.pop((process_set_id, name), None)

    # -- core side (background thread) ------------------------------------

    def _execute(self, op_class, n, names_p, shapes_p, dtype, reduce_op,
                 root_rank, ps_id, sizes_p, n_sizes, err_p, err_cap):
        # One span a fused response, on the core's thread: take the
        # inputs, look the program up (or build it), launch, store the
        # outputs. The launch is asynchronous; the device's time is in
        # the device trace under jit_hvd_<op>.
        try:
            with span("hvd.device_exec") as s:
                names = [names_p[i].decode() for i in range(n)]
                shapes = _decode_shapes(shapes_p, n)
                np_dtype = _ENUM_TO_DTYPE[dtype]
                rank_sizes = tuple(int(sizes_p[i]) for i in range(n_sizes))
                cached = len(self._exec_cache)
                self._run(op_class, names, shapes, np_dtype, reduce_op,
                          root_rank, ps_id, rank_sizes)
                if s.is_enabled():
                    s.set_metadata(
                        op=_OP_NAMES.get(op_class, op_class), tensors=n,
                        bytes=sum(map(_nelem, shapes)) * np_dtype.itemsize,
                        executable_cache="miss" if len(self._exec_cache)
                        > cached else "hit")
            return 0
        except Exception as e:  # noqa: BLE001 — crosses the C boundary
            msg = f"xla_ici: {type(e).__name__}: {e}".encode()[:err_cap - 1]
            ctypes.memmove(err_p, msg + b"\0", len(msg) + 1)
            return 1

    def _members(self, ps_id):
        members = process_sets.members_of(ps_id)
        if members is None:
            raise ValueError(f"unknown process set {ps_id}")
        return tuple(members)

    def _take_inputs(self, names, shapes, np_dtype, ps_id):
        """Local contributions in fused order; zeros for names this rank
        never enqueued (join support). Third return: whether EVERY input
        in the group was registered with donate=True (donation is
        all-or-nothing per fused program)."""
        arrs, scales = [], []
        donate = True
        with self._lock:
            pending = [self._inputs.pop((ps_id, nm), None) for nm in names]
        for nm, shape, p in zip(names, shapes, pending):
            if p is None:
                arrs.append(jnp.zeros(shape, np_dtype))
                scales.append((1.0, 1.0))
                donate = False
            else:
                arr, pre, post, don = p
                if arr.dtype != np_dtype:
                    arr = arr.astype(np_dtype)
                arrs.append(arr)
                scales.append((pre, post))
                donate = donate and don
        return arrs, tuple(scales), donate

    def _mesh(self, members):
        return Mesh(np.array([self._devices[r] for r in members]), ("hvd",))

    def _global(self, mesh, group, local_2d):
        """Lift this rank's (1, k) block to the global (group, k) array."""
        shard = jax.device_put(local_2d, self._local_device)
        return jax.make_array_from_single_device_arrays(
            (group,) + tuple(local_2d.shape[1:]),
            NamedSharding(mesh, P("hvd")), [shard])

    def _global_rows(self, mesh, group, arr):
        """Lift this rank's array to the global one stacked over ranks
        along dim 0 — no staging copy: the local buffer IS the shard, in
        its own shape (a scalar rides as shape (1,))."""
        local = jax.device_put(arr.reshape(1) if arr.ndim == 0 else arr,
                               self._local_device)
        return jax.make_array_from_single_device_arrays(
            (group * local.shape[0],) + tuple(local.shape[1:]),
            NamedSharding(mesh, P("hvd")), [local])

    def _store(self, names, ps_id, outs):
        with self._lock:
            for nm, o in zip(names, outs):
                self._outputs[(ps_id, nm)] = o

    def _run(self, op_class, names, shapes, np_dtype, reduce_op, root_rank,
             ps_id, rank_sizes):
        members = self._members(ps_id)
        group = len(members)
        mesh = self._mesh(members)
        if op_class == _OP_ALLREDUCE:
            arrs, scales, donate = self._take_inputs(names, shapes,
                                                     np_dtype, ps_id)
            sig = (op_class, members, np_dtype.str, tuple(shapes), reduce_op,
                   scales, donate)
            fn = self._exec_cache.get(sig)
            new = fn is None
            if new:
                if group == 1:
                    fn = _build_allreduce_local(reduce_op, scales, donate)
                else:
                    fn = _build_allreduce(mesh, group, shapes, reduce_op,
                                          scales, donate)
                self._exec_cache[sig] = fn
            if group == 1:
                if new:
                    register_program(fn, *arrs)
                # Single-member set: the reduction is identity × scales,
                # so the program is just the scales and, with donation,
                # the outputs alias the inputs outright (zero HBM
                # transient). One executable call, no per-tensor lifts.
                outs = list(fn(*arrs))
                del arrs
                self._store(names, ps_id, outs)
                return
            # Lift each tensor IN ITS OWN SHAPE: the local buffer is the
            # shard, so nothing is staged or copied on the way in, and
            # with donation the program reduces in place (per-device
            # input and output shapes are identical). The first cut of
            # this path flattened every tensor to a [1, k] row and
            # concatenated them — ~5x the payload in HBM, and a [1, k]
            # reshape of a 0.7 GB gradient did not finish compiling in
            # 400 s on a v5e (PR 21).
            gins = []
            for i in range(len(arrs)):
                gins.append(self._global_rows(mesh, group, arrs[i]))
                arrs[i] = None
            del arrs
            if new:   # the program files itself (spans.scope_tables)
                register_program(fn, *gins)
            outs = [g.addressable_data(0).reshape(shape)
                    if not shape else g.addressable_data(0)
                    for g, shape in zip(fn(*gins), shapes)]
            self._store(names, ps_id, outs)
        elif op_class == _OP_BROADCAST:
            arrs, _, _ = self._take_inputs(names, shapes, np_dtype, ps_id)
            root_pos = members.index(root_rank)
            sig = (op_class, members, np_dtype.str, tuple(shapes), root_pos)
            fn = self._exec_cache.get(sig)
            if fn is None:
                fn = _build_broadcast(mesh, root_pos)
                self._exec_cache[sig] = fn
            g = self._global(mesh, group, arrs[0].reshape(1, -1))
            out = fn(g).addressable_data(0).reshape(shapes[0])
            self._store(names, ps_id, [out])
        elif op_class == _OP_ALLGATHER:
            # rank_sizes: per-member first dims (ragged allgather). This
            # rank's contribution is zero-padded to the max first dim so
            # shards are uniform; the program slices the padding back out.
            shape = shapes[0]
            rest = shape[1:] if shape else ()
            dims = rank_sizes if rank_sizes else (shape[0] if shape else 1,)
            max_d = max(max(dims), 1)
            my_rows = dims[members.index(self._rank)]
            arrs, _, _ = self._take_inputs(
                names, [(my_rows,) + rest], np_dtype, ps_id)
            local = arrs[0].reshape(my_rows, _row_elems(rest))
            pad = max_d - local.shape[0]
            if pad:
                local = jnp.concatenate(
                    [local, jnp.zeros((pad, local.shape[1]), np_dtype)])
            sig = (op_class, members, np_dtype.str, dims, rest)
            fn = self._exec_cache.get(sig)
            if fn is None:
                fn = _build_allgather(mesh, dims)
                self._exec_cache[sig] = fn
            g = self._global(mesh, group, local[None])
            out = fn(g).addressable_data(0).reshape((sum(dims),) + rest)
            self._store(names, ps_id, [out])
        elif op_class == _OP_ALLTOALL:
            # Equal splits only (the coordinator enforces identical
            # shapes): rank r's block j goes to rank j, landing at
            # position r — one lax.all_to_all, static shapes.
            shape = shapes[0]
            first = shape[0] if shape else 1
            rest = shape[1:] if shape else ()
            if first % group:
                raise ValueError(
                    f"device alltoall first dim {first} not divisible by "
                    f"group size {group}")
            arrs, _, _ = self._take_inputs(names, shapes, np_dtype, ps_id)
            sig = (op_class, members, np_dtype.str, tuple(shape))
            fn = self._exec_cache.get(sig)
            if fn is None:
                fn = _build_alltoall(mesh, group)
                self._exec_cache[sig] = fn
            g = self._global(mesh, group,
                             arrs[0].reshape(1, first, _row_elems(rest)))
            out = fn(g).addressable_data(0).reshape((first,) + rest)
            self._store(names, ps_id, [out])
        elif op_class == _OP_REDUCESCATTER:
            arrs, scales, _ = self._take_inputs(names, shapes, np_dtype,
                                                ps_id)
            shape = shapes[0]
            first = shape[0] if shape else 1
            rest = shape[1:] if shape else ()
            # First dim split as evenly as possible, remainder to lower
            # member positions — same convention as the host ring
            # (csrc/operations.cc REDUCESCATTER).
            q, rem = divmod(first, group)
            rows = [q + (1 if r < rem else 0) for r in range(group)]
            my_pos = members.index(self._rank)
            off = sum(rows[:my_pos])
            sig = (op_class, members, np_dtype.str, tuple(shape), reduce_op,
                   scales, my_pos)
            fn = self._exec_cache.get(sig)
            if fn is None:
                fn = _build_reducescatter(mesh, group, reduce_op, scales[0],
                                          off, rows[my_pos])
                self._exec_cache[sig] = fn
            g = self._global(mesh, group,
                             arrs[0].reshape(1, first, _row_elems(rest)))
            out = fn(g).addressable_data(0).reshape((rows[my_pos],) + rest)
            self._store(names, ps_id, [out])
        else:
            raise ValueError(f"unsupported device op_class {op_class}")


def _shard_map(fn, mesh, in_specs, out_specs):
    # check_vma off: outputs ARE replicated (psum/pmin/... results), but
    # the checker can't always prove it through the slice/scale epilogue.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _adasum_combine(x, group):
    """Adasum on the device plane: recursive-doubling pairwise combine
    (reference analog: ops/adasum_gpu_operations.cc — a first-class GPU
    op upstream; here one XLA program over the mesh axis).

    Each stage pairs rank i with i^d and combines
    ``(1 - a.b/(2|a|^2)) a + (1 - a.b/(2|b|^2)) b`` — symmetric, so both
    partners hold the identical result and distances double. Dots run in
    fp32 regardless of payload dtype (csrc/adasum.cc does the same for
    half/bf16). Requires a power-of-two group; the frontend falls back
    to the host path otherwise.
    """
    orig = x.dtype
    x = x.astype(jnp.float32)
    d = 1
    while d < group:
        y = lax.ppermute(x, "hvd", [(i, i ^ d) for i in range(group)])
        dot = jnp.sum(x * y)
        na = jnp.sum(x * x)
        nb = jnp.sum(y * y)
        ca = jnp.where(na > 0, 1.0 - dot / (2.0 * na), 1.0)
        cb = jnp.where(nb > 0, 1.0 - dot / (2.0 * nb), 1.0)
        x = ca * x + cb * y
        d *= 2
    return x.astype(orig)


def _reduce(buf, reduce_op, group):
    """Reduce ``buf`` — one array, or a tuple reduced as ONE variadic
    collective — over the "hvd" axis."""
    if reduce_op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        red = lax.psum(buf, "hvd")
        if reduce_op == ReduceOp.AVERAGE:
            red = jax.tree.map(
                lambda r: r / group
                if jnp.issubdtype(r.dtype, jnp.floating) else r // group,
                red)
        return red
    if reduce_op == ReduceOp.MIN:
        return lax.pmin(buf, "hvd")
    if reduce_op == ReduceOp.MAX:
        return lax.pmax(buf, "hvd")
    if reduce_op == ReduceOp.PRODUCT:
        return jax.tree.map(
            lambda b: jnp.prod(lax.all_gather(b, "hvd"), axis=0), buf)
    raise ValueError(f"reduce op {reduce_op} is not supported on the XLA "
                     "data plane (Adasum rides the host path)")


def _build_allreduce_local(reduce_op, scales, donate):
    """The group-size-1 allreduce program: every reduce op over a single
    member is the identity (sum/avg/min/max/product of one contribution;
    Adasum's pairwise combine has no partner), so the compiled program
    is just the pre/post scales — and with donation, pure buffer
    aliasing. Original shapes in, original shapes out."""

    @scope("hvd.allreduce")
    def hvd_allreduce(*xs):
        outs = []
        for x, (pre, post) in zip(xs, scales):
            if pre != 1.0:
                x = x * np.asarray(pre, x.dtype)
            if post != 1.0:
                x = x * np.asarray(post, x.dtype)
            outs.append(x)
        return tuple(outs)

    return jax.jit(
        hvd_allreduce,
        donate_argnums=tuple(range(len(scales))) if donate else ())


def _build_allreduce(mesh, group, shapes, reduce_op, scales, donate=False):
    """One program for the fused group: prescale → ONE variadic
    collective over every tensor in its own shape → postscale. This IS
    the fusion buffer — XLA combines the operands of the collective
    itself, with no concat/split copies and no flattening (reference
    analog: MemcpyInFusionBuffer + cuda_kernels.cu, done here by the
    compiler). Each device's block is the tensor as the frontend handed
    it over and each output block is the reduced tensor in the same
    shape, so ``donate=True`` lets the program reduce in place
    (reference analog: the in-place fusion buffer — safe only when the
    frontend promised the inputs are dead, see
    ``enqueue_device(donate=...)``)."""

    @scope("hvd.allreduce")
    def hvd_allreduce(*blocks):
        parts = tuple(
            b * np.asarray(pre, b.dtype) if pre != 1.0 else b
            for b, (pre, _) in zip(blocks, scales))
        if reduce_op == ReduceOp.ADASUM:
            # Adasum is PER-TENSOR (the dot products that make it scale
            # insensitive are per-gradient — reference
            # Adasum::DispatchFusedAllreduce walks the fusion buffer
            # tensor-by-tensor); the stages still share the program and
            # its collectives schedule.
            red = tuple(_adasum_combine(p, group) for p in parts)
        else:
            red = _reduce(parts, reduce_op, group)
        return tuple(
            o * np.asarray(post, o.dtype) if post != 1.0 else o
            for o, (_, post) in zip(red, scales))

    # Every device emits its own copy of the result as "its shard": the
    # global output mirrors the global input, which is what lets a
    # donated input alias it.
    k = len(shapes)
    return jax.jit(_shard_map(hvd_allreduce, mesh, (P("hvd"),) * k,
                              (P("hvd"),) * k),
                   donate_argnums=tuple(range(k)) if donate else ())


def _build_broadcast(mesh, root_pos):
    def hvd_broadcast(block):  # (1, n)
        x = block.reshape(-1)
        idx = lax.axis_index("hvd")
        if jnp.issubdtype(x.dtype, jnp.bool_):
            contrib = jnp.where(idx == root_pos, x.astype(jnp.uint8),
                                jnp.zeros_like(x, jnp.uint8))
            return lax.psum(contrib, "hvd").astype(jnp.bool_)
        contrib = jnp.where(idx == root_pos, x, jnp.zeros_like(x))
        return lax.psum(contrib, "hvd")

    return jax.jit(_shard_map(hvd_broadcast, mesh, P("hvd"), P(None)))


def _build_allgather(mesh, dims):
    def hvd_allgather(block):  # (1, max_d, restf)
        g = lax.all_gather(block[0], "hvd")  # (group, max_d, restf)
        segs = [lax.slice_in_dim(g[i], 0, d) for i, d in enumerate(dims)]
        return jnp.concatenate(segs, axis=0)

    return jax.jit(_shard_map(hvd_allgather, mesh, P("hvd"), P(None)))


def _build_alltoall(mesh, group):
    def hvd_alltoall(block):  # (1, first, restf)
        x = block[0]
        first, restf = x.shape
        x = x.reshape(group, first // group, restf)
        y = lax.all_to_all(x, "hvd", split_axis=0, concat_axis=0)
        return y.reshape(1, first, restf)

    # Output differs per rank: stays sharded over "hvd", each process
    # reads its own shard.
    return jax.jit(_shard_map(hvd_alltoall, mesh, P("hvd"), P("hvd")))


def _build_reducescatter(mesh, group, reduce_op, scale, off, nrows):
    pre, post = scale

    def hvd_reducescatter(block):  # (1, first, restf)
        x = block[0]
        if pre != 1.0:
            x = x * np.asarray(pre, x.dtype)
        red = _reduce(x, reduce_op, group)
        out = lax.slice_in_dim(red, off, off + nrows)
        if post != 1.0:
            out = out * np.asarray(post, out.dtype)
        return out

    return jax.jit(_shard_map(hvd_reducescatter, mesh, P("hvd"), P(None)))


# Module-level singleton; frontends share it.
_data_plane = XlaIciDataPlane()


def cross_plane_mode():
    """The job's cross-plane topology descriptor — the core's parsed
    ``HOROVOD_CROSS_PLANE`` when it is initialized (covers the legacy
    ``HOROVOD_HIERARCHICAL_ALLREDUCE`` mapping), else the raw env.
    One of ``"auto" | "ici" | "ring" | "hier"``."""
    if _basics.lib.hvdtpu_is_initialized():
        return HorovodBasics.CROSS_PLANE_MODES[
            _basics.lib.hvdtpu_cross_plane()]
    mode = os.environ.get("HOROVOD_CROSS_PLANE", "").strip().lower()
    if mode in HorovodBasics.CROSS_PLANE_MODES:
        return mode
    if os.environ.get("HOROVOD_HIERARCHICAL_ALLREDUCE", "0") not in \
            ("", "0"):
        return "hier"
    return "auto"


def data_plane():
    return _data_plane


def active():
    return _data_plane.active


def enable():
    _data_plane.enable()


def disable():
    _data_plane.disable()


class DeviceHandle:
    """An in-flight device collective; ``synchronize`` returns the jax
    array produced by the data plane (payload never left HBM)."""

    def __init__(self, raw, name, process_set_id):
        self._raw = raw
        self._name = name
        self._ps = process_set_id
        self._done = False

    def poll(self):
        rc = _basics.lib.hvdtpu_poll(self._raw)
        if rc < 0:
            raise ValueError(f"invalid Horovod handle {self._raw}")
        return rc == 1

    def synchronize(self):
        if self._done:
            raise ValueError("handle already synchronized")
        lib = _basics.lib
        rc = lib.hvdtpu_wait(self._raw)
        self._done = True
        if rc != 0:
            err = lib.hvdtpu_error_string(self._raw)
            msg = err.decode() if err else "unknown error"
            lib.hvdtpu_release(self._raw)
            _data_plane.drop(self._name, self._ps)
            raise HorovodInternalError(msg)
        lib.hvdtpu_release(self._raw)
        return _data_plane.pop_output(self._name, self._ps)


# Response::ResponseType values accepted by hvdtpu_enqueue_device.
_ENQUEUE_OPS = {name: op for op, name in _OP_NAMES.items()}


def alltoall_group_size(process_set_id):
    """Member count of the set, for the frontend's equal-split check."""
    members = process_sets.members_of(int(process_set_id))
    return len(members) if members else 0


def adasum_device_supported(process_set_id, dtype):
    """Device-plane Adasum serves power-of-two float groups; anything
    else rides the host path (csrc/adasum.cc)."""
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return False
    n = alltoall_group_size(process_set_id)
    return n > 0 and (n & (n - 1)) == 0


def enqueue_device(kind, array, name, reduce_op=ReduceOp.SUM,
                   prescale_factor=1.0, postscale_factor=1.0, root_rank=0,
                   process_set_id=0, group_id=-1, group_size=0,
                   donate=False):
    """Register the device array and enqueue its negotiation-only request.

    The returned DeviceHandle's ``synchronize()`` yields the result as a
    jax array on this rank's device.

    ``donate=True`` (allreduce only) promises the caller will not read
    ``array`` again: the fused program then donates its HBM to the
    result, halving the collective's peak footprint. The input array is
    INVALID afterwards (jax donation semantics) — never set this for
    buffers aliased outside jax (e.g. the torch dlpack bridge).
    """
    ps_id = int(process_set_id)
    arr = _data_plane.register_input(name, ps_id, array, prescale_factor,
                                     postscale_factor, donate=donate)
    shape = (ctypes.c_int64 * max(arr.ndim, 1))(*arr.shape)
    dtype = _DTYPE_TO_ENUM[np.dtype(arr.dtype)]
    h = _basics.lib.hvdtpu_enqueue_device(
        _ENQUEUE_OPS[kind], name.encode(), arr.ndim, shape, dtype,
        int(reduce_op), int(root_rank), ps_id, int(group_id),
        int(group_size))
    if h < 0:
        _data_plane.drop(name, ps_id)
        raise RuntimeError(f"failed to enqueue device {kind} (is the XLA "
                           "data plane enabled and Horovod running?)")
    return DeviceHandle(h, name, ps_id)


def grouped_allreduce_device(tensors, names, reduce_op=ReduceOp.SUM,
                             prescale_factor=1.0, postscale_factor=1.0,
                             process_set_id=0, donate=False):
    """Atomically-negotiated grouped allreduce on device arrays: all
    tensors fuse into ONE XLA program (reference analog: grouped
    allreduce via group_table.cc, on the device data plane).

    Validates BEFORE enqueueing anything: a half-enqueued atomic group
    can never complete, hanging every member rank.
    """
    if len(names) != len(tensors):
        raise ValueError(f"grouped_allreduce: {len(tensors)} tensors but "
                         f"{len(names)} names")
    if len(set(names)) != len(names):
        raise ValueError(f"grouped_allreduce: duplicate names in {names}")
    if not (_data_plane.active and _basics.is_initialized()):
        raise RuntimeError("grouped_allreduce_device requires hvd.init() "
                           "and an active XLA data plane")
    gid = _basics.lib.hvdtpu_next_group_id() if len(tensors) > 1 else -1
    return [enqueue_device("allreduce", t, nm, reduce_op=reduce_op,
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor,
                           process_set_id=process_set_id, group_id=gid,
                           group_size=len(tensors), donate=donate)
            for t, nm in zip(tensors, names)]
