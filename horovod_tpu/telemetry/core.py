"""Parsed access to the native core's metrics registry.

Thin on purpose: the counters live in C++ (``csrc/metrics.h``) where
the background loop records them for free; this module only parses the
JSON snapshot and derives the handful of aggregates the exporters and
:class:`~horovod_tpu.telemetry.step_timer.StepTimer` need.
"""

from horovod_tpu.common.basics import HorovodBasics

_basics = HorovodBasics()


def snapshot():
    """One point-in-time dict of every core counter.

    Safe to call at any moment (before ``hvd.init()`` it returns zeroed
    counters with ``initialized: False``); cheap enough for per-step
    use — one ctypes call plus a small ``json.loads``. Counters are
    monotonic for the process lifetime: consumers diff snapshots rather
    than resetting (see ``docs/metrics.md`` for the catalog).
    """
    return _basics.metrics_snapshot()


def metrics_reset():
    """Zero the registry (tests / interactive sessions only). Also
    forgets the open window's owner: the core discards the window
    itself, so a stale owner would wrongly block the next driver."""
    global _window_owner
    _basics.metrics_reset()
    _window_owner = None


def wire_bytes(snap=None):
    """``(tx_bytes, tx_logical_bytes)`` of the host-ring transport.

    ``tx_bytes`` is what actually crossed the wire; ``tx_logical_bytes``
    the same traffic at full tensor width. They diverge exactly by the
    bf16 wire-compression saving (``HOROVOD_WIRE_COMPRESSION``, see
    ``docs/wire.md``) — and both differ from :func:`total_collective_bytes`,
    which counts logical PAYLOAD (the ring moves ~2(N-1)/N x payload
    per rank).
    """
    if snap is None:
        snap = snapshot()
    w = snap.get("wire", {})
    return w.get("tx_bytes", 0), w.get("tx_logical_bytes", 0)


def wire_plane_bytes(snap=None):
    """Per-plane transport tx accounting as a 4-tuple
    ``(intra_tx, intra_tx_logical, cross_tx, cross_tx_logical)``.

    The core books the cross-slice hop of the hierarchical
    decomposition separately (``wire.cross_*``, the DCN-priced fabric
    — docs/redistribute.md) *inside* the totals, so intra here is
    total minus cross. The pair of pairs lets per-plane goodput and
    compression ratios reconcile independently (cross-hop-only bf16
    moves cross to ~0.5 while intra stays 1.0).
    """
    if snap is None:
        snap = snapshot()
    w = snap.get("wire", {})
    cross = w.get("cross_tx_bytes", 0)
    cross_l = w.get("cross_tx_logical_bytes", 0)
    return (w.get("tx_bytes", 0) - cross,
            w.get("tx_logical_bytes", 0) - cross_l, cross, cross_l)


#: who opened the currently-open step window (None = no window, or a
#: legacy caller that did not declare itself). Owner strings in use:
#: "StepTimer" (explicit scope) and "optimizer" (the fused optimizer's
#: implicit boundary). Core step ids RESTART after metrics_reset(), so
#: id comparison alone cannot tell "my window" from "someone else's
#: window that reused my id" — the owner can.
_window_owner = None


def step_mark(begin=True, owner=None):
    """Mark a step boundary (see ``HorovodBasics.step_mark``); returns
    the step id. The StepTimer calls this at its own boundaries so the
    core-side overlap ledger and the Python wall clock scope the same
    window.

    ``owner`` names the driver opening the window; it is recorded
    python-side (:func:`window_owner`) so the two step-scoping drivers
    — an explicit StepTimer scope and the fused optimizer's implicit
    boundary — can detect each other and keep ONE owner per window
    instead of silently fragmenting the overlap ledger's attribution.
    A ``begin=False`` close always clears the owner.
    """
    global _window_owner
    sid = _basics.step_mark(begin)
    _window_owner = owner if begin else None
    return sid


def step_id():
    """The currently open step id, or -1."""
    return _basics.step_id()


def window_owner():
    """Who opened the currently-open step window (``step_mark``'s
    ``owner``), or None when no window is open / the opener did not
    declare itself."""
    return _window_owner


def wire_overlap(snap=None):
    """The per-step wire overlap ledger (``wire.overlap`` of the
    snapshot, docs/metrics.md): cumulative + last-step exposed/hidden/
    total wire time per plane, and the combined ``overlap_efficiency``.
    Empty dict when the core is unavailable."""
    if snap is None:
        snap = snapshot()
    return snap.get("wire", {}).get("overlap", {})


def events(last_n=0):
    """The newest ``last_n`` structured ring events (non-consuming;
    see ``docs/metrics.md`` for the event catalog)."""
    return _basics.events(last_n)


def events_drain():
    """Consume and return every ring event since the last drain."""
    return _basics.events_drain()


def total_collective_bytes(snap=None, planes=("ops", "device_ops"),
                           op_classes=None):
    """Sum payload bytes across op classes and planes of a snapshot.

    ``op_classes`` restricts the sum (e.g. ``("allreduce",)`` for
    gradient-traffic accounting); default is everything that moved.
    """
    if snap is None:
        snap = snapshot()
    total = 0
    for plane in planes:
        for op, counters in snap.get(plane, {}).items():
            if op_classes is not None and op not in op_classes:
                continue
            total += counters.get("bytes", 0)
    return total
