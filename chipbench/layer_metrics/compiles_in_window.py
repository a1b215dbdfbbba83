"""Programs traced or compiled while the window's steps ran, priming
included, by the program's own compile log
(``horovod_tpu.utils.compile_cache.compile_events()``; docs/metrics.md
"Set-up: the compile log and the start-up marks"): ``trace`` and
``compile`` records whose ``at_step`` lies in the window (a record is an
outermost trip: a ``jit`` traced inside another is none). 0 in a sound
run: every shape was warmed up before the window opened. ``None``
for a program without the log.

:func:`split` is what the readers of the log share: the log has no clock
of the benchmark's, it tells set-up from window from checks by the
program's own count of steps. The eager lane counts a step in
``allreduce_gradients``, so there the user's grad program of a step
carries the count of the step before."""


def split(ctx):
    """The records of the compile log -> ``(set-up, window, after)``,
    or ``None`` for a program without the log. Set-up: up to the return
    of the last calibration step (``at_step`` <= warm-up + calibration
    steps). After: once the window's last step (``ctx.steps_in_window``
    more, priming included) has returned: the output checks and the
    readers before this one. The window: what lies between."""
    try:
        from horovod_tpu.utils.compile_cache import compile_events
    except ImportError:
        return None
    opens = ctx.traffic["warmup_steps"] + ctx.traffic["calibration_steps"]
    closes = opens + ctx.steps_in_window
    setup, window, after = [], [], []
    for e in compile_events():
        if e.at_step <= opens:
            setup.append(e)
        elif e.at_step > closes or (e.at_step == closes and not e.in_step):
            after.append(e)
        else:
            window.append(e)
    return setup, window, after


def setup_seconds(ctx, of):
    """``of(record)`` summed over the set-up's records; 0.0 where it has
    none, ``None`` for a program without the log."""
    parts = split(ctx)
    if parts is None:
        return None
    return float(sum(of(e) for e in parts[0]))


def read(ctx):
    parts = split(ctx)
    if parts is None:
        return None
    return float(sum(e.phase in ("trace", "compile") for e in parts[1]))
