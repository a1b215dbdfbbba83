"""The spans the program itself writes into the profiler's trace
(docs/metrics.md "Program spans"): one per layer boundary, never one per
tensor. A leaf module: it imports ``jax.profiler`` and nothing of this
package, so the lowest layers can open a span without pulling in
``horovod_tpu.telemetry``."""

from jax.profiler import TraceAnnotation

SPANS = frozenset({"hvd.enqueue", "hvd.device_exec", "hvd.wait",
                   "hvd.spmd.step"})


def span(name, **carries):
    """A ``jax.profiler.TraceAnnotation`` under a name of :data:`SPANS`.

    The span lands on the ``/host:CPU`` plane of the same xplane file
    as the device ops, on the profiler's clock, on the thread that
    opened it. With no trace being taken it records nothing: it costs
    the name check and the profiler's flag test. ``carries`` become the
    event's stats; a value that is not free to compute is attached
    under ``if s.is_enabled(): s.set_metadata(...)`` instead.
    """
    if name not in SPANS:
        raise ValueError(f"{name!r} is not a program span: {sorted(SPANS)}")
    return TraceAnnotation(name, **carries)
