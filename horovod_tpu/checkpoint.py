"""Checkpoint engine: async, sharded, TPU-idiomatic (orbax).

Reference analog: the reference has NO core checkpoint engine
(SURVEY.md §5.4) — it delegates to the frameworks: elastic ``State``
commits to host memory, Keras callbacks save on rank 0, Spark
estimators write to the ``Store``. This module is the TPU-idiomatic
engine those layers compose with: orbax handles sharded jax pytrees
(on multi-host meshes every process writes exactly its own shards) and
async save (training continues while the previous step flushes).

One-shot::

    from horovod_tpu import checkpoint as ckpt
    ckpt.save(path, {"params": params, "opt": opt_state})
    state = ckpt.restore(path, target=abstract_state)

Step-managed::

    mgr = ckpt.CheckpointManager(dir, max_to_keep=3)
    mgr.save(step, state)          # async; returns immediately
    state = mgr.restore(target=abstract_state)   # latest step
    mgr.wait(); mgr.close()

Rank policy: with a single jax process but multiple Horovod ranks
(host-ring data parallelism), only rank 0 writes — replicas hold
identical state, and concurrent writers to one directory would race.
With ``jax.distributed`` initialized (TPU pods / the xla_ici plane),
every process participates — orbax coordinates the multi-host write
through ``jax.distributed``'s client, and a rank's own replica of the
state is written by orbax's primary process.
"""

import os

import jax

from horovod_tpu.common.basics import HorovodBasics

_basics = HorovodBasics()


def _i_write():
    """Whether this rank takes part in the write (see module docstring)."""
    if jax.process_count() > 1:
        return True
    if not _basics.is_initialized():
        return True  # standalone use outside a Horovod job
    return _basics.rank() == 0


def _ocp():
    import orbax.checkpoint as ocp

    return ocp


_PICKLE_KEY = "__hvd_pickle__"


def encode_pytree(tree):
    """Replace leaves orbax can't store (strings, arbitrary objects)
    with pickled uint8 buffers, marked for :func:`decode_pytree`."""
    import pickle

    import numpy as np

    def enc(x):
        try:
            if np.asarray(x).dtype.kind in "biufc?":
                return x
        except Exception:  # noqa: BLE001 — not arrayable at all
            pass
        return {_PICKLE_KEY: np.frombuffer(pickle.dumps(x),
                                           np.uint8).copy()}

    return jax.tree.map(enc, tree)


def decode_pytree(tree):
    """Inverse of :func:`encode_pytree`."""
    import pickle

    import numpy as np

    def is_marker(x):
        return isinstance(x, dict) and set(x) == {_PICKLE_KEY}

    def dec(x):
        if is_marker(x):
            return pickle.loads(np.asarray(x[_PICKLE_KEY]).tobytes())
        return x

    return jax.tree.map(dec, tree, is_leaf=is_marker)


def _storable(state):
    """The leaves as orbax's StandardCheckpointHandler takes them.

    It restricts leaves to ``(int, float, np.ndarray, jax.Array)`` on
    recent versions (0.7.x validates on save); numpy SCALARS
    (``np.int64(7)`` — the natural type of a step counter) fail that
    check. Promote them to 0-d ndarrays, which round-trip equivalently
    (``int(x)``/``float(x)`` and arithmetic behave the same on restore).

    With one jax process per rank it also refuses a ``jax.Array`` that
    lives on this process alone ("Cannot serialize host local
    jax.Array"), which is what a Horovod rank's parameters are: every
    rank holds a replica on its own chip. Such a leaf goes in as the
    host's copy, which orbax's primary process writes. Arrays sharded
    over the processes stay arrays: every process writes its shards.
    """
    import numpy as np

    one_process_a_rank = jax.process_count() > 1

    def storable(x):
        if isinstance(x, np.generic) or (
                one_process_a_rank and isinstance(x, jax.Array)
                and x.is_fully_addressable):
            return np.asarray(x)
        return x

    return jax.tree.map(storable, state)


def save(path, state, force=True, sync=False):
    """Synchronous one-shot save of a pytree (jax arrays, numpy, scalars).

    ``force`` overwrites an existing checkpoint at ``path``. On the
    host-ring (single jax process, many Horovod ranks) only rank 0
    writes; non-writer ranks return IMMEDIATELY, so a rank that wants to
    restore right after must synchronize first — either pass
    ``sync=True`` (runs a Horovod barrier; then EVERY rank must call
    save, or the job hangs) or barrier explicitly.
    """
    if _i_write():
        ocp = _ocp()
        with ocp.StandardCheckpointer() as cp:
            cp.save(os.path.abspath(os.fspath(path)),
                    _storable(state), force=force)
    if sync and _basics.is_initialized() and _basics.size() > 1:
        from horovod_tpu.common import eager_ops

        eager_ops.barrier()


def restore(path, target=None):
    """Restore a pytree saved by :func:`save`.

    ``target`` (optional) is a pytree of like-structured arrays or
    ``jax.ShapeDtypeStruct`` with shardings — pass it to restore
    directly into a sharded layout on a mesh; without it, values come
    back as host arrays in the saved structure.
    """
    ocp = _ocp()
    with ocp.StandardCheckpointer() as cp:
        return cp.restore(os.path.abspath(os.fspath(path)), target)


class CheckpointManager:
    """Step-numbered checkpoints with retention and async save.

    Reference analog: the Keras ``ModelCheckpoint``-on-rank-0 pattern
    and Spark's Store, unified on one engine.
    """

    def __init__(self, directory, max_to_keep=3, async_save=True):
        self._dir = os.path.abspath(os.fspath(directory))
        self._mgr = None
        self._options = _ocp().CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save)
        self._ensure_role()

    def _ensure_role(self):
        """(Re-)evaluate whether this rank writes. Elastic re-rendezvous
        reassigns Horovod ranks, so writer status cannot be frozen at
        construction: a departed rank 0 must hand the manager to the new
        rank 0, and a demoted one must stop writing."""
        writer = _i_write()
        if writer and self._mgr is None:
            self._mgr = _ocp().CheckpointManager(self._dir,
                                                 options=self._options)
        elif not writer and self._mgr is not None:
            self._mgr.wait_until_finished()
            self._mgr.close()
            self._mgr = None
        return self._mgr

    def save(self, step, state, wait=False):
        """Queue an async save of ``state`` under ``step``. ``wait``
        blocks until it is durable (otherwise the next save or
        :meth:`wait` joins it). Returns False on non-writer ranks and
        when orbax skips the step (already on disk)."""
        if self._ensure_role() is None:
            return False
        ocp = _ocp()
        saved = self._mgr.save(
            int(step), args=ocp.args.StandardSave(_storable(state)))
        if wait:
            self._mgr.wait_until_finished()
        return saved

    def latest_step(self):
        if self._mgr is None:
            # Non-writer ranks can still read the directory.
            ocp = _ocp()
            with ocp.CheckpointManager(self._dir) as mgr:
                return mgr.latest_step()
        return self._mgr.latest_step()

    def restore(self, step=None, target=None):
        """Restore ``step`` (default: latest). See :func:`restore` for
        ``target``. Every rank may call this."""
        ocp = _ocp()
        mgr = self._mgr
        own = False
        if mgr is None:
            mgr = ocp.CheckpointManager(self._dir)
            own = True
        try:
            if step is None:
                step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint found under {self._dir}")
            # args ALWAYS passed (StandardRestore(None) = saved
            # structure): a bare mgr.restore(step) only works when the
            # SAME manager object did the save — a fresh manager (the
            # resume-after-restart path) has no handler registered for
            # the item and orbax >= 0.7 raises KeyError asking for a
            # CheckpointArgs subclass.
            return mgr.restore(
                int(step), args=ocp.args.StandardRestore(target))
        finally:
            if own:
                mgr.close()

    def wait(self):
        if self._mgr is not None:
            self._mgr.wait_until_finished()

    def close(self):
        if self._mgr is not None:
            self._mgr.wait_until_finished()
            self._mgr.close()
            self._mgr = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
