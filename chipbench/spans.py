"""Program spans of a traced run, per step: what the readers of the
``hvd.*`` spans share. The program writes these spans itself
(``horovod_tpu/utils/spans.py``; docs/metrics.md "Program
spans") onto the ``/host:CPU`` plane of the same file as the device ops,
so they are cut to the chip's window of whole steps and divided by its
steps like any device time. A program that writes no such span (one from
before PR 25) gives ``None``, never 0."""

from chipbench import xplane


def ms_per_step(ctx, name):
    """Time inside spans called ``name`` within the window of whole
    steps, in ms a step; a span the window's edge cuts counts as far as
    it lies inside. No program span opens inside another on the same
    thread (``hvd.device_exec`` falls into ``hvd.wait``'s interval, but
    runs on the core's thread while the user's sleeps), so this is the
    span's own time."""
    chip = ctx.chip
    if not chip.steps:
        return None
    spans = xplane.union(
        (s, e) for _, s, e in xplane.host_spans(ctx.profile, (name,)))
    if not spans:
        return None
    return xplane.total(xplane.clip(spans, chip.t0, chip.t1)) \
        / 1e6 / chip.steps


def counter_per_step(ctx, *path, scale=1.0):
    """Growth of one core counter (``hvd.metrics()`` at ``path``) over
    the window, per step of the window; ``None`` where the lane has no
    such counter."""
    def at(snap):
        for key in path:
            if not isinstance(snap, dict) or key not in snap:
                return None
            snap = snap[key]
        return snap

    before, after = (at(snap) for snap in ctx.counters)
    if before is None or after is None or not ctx.steps_in_window:
        return None
    return (after - before) * scale / ctx.steps_in_window
