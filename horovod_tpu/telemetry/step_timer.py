"""Per-step wall-time, MFU, goodput, and bubble accounting.

:class:`StepTimer` is the step-level half of the telemetry subsystem:
the core registry (``telemetry.snapshot()``) counts what the runtime
moved; the timer relates those counters to *steps* — wall time per
step, model-FLOPs utilization from compiled cost analysis, wire
goodput, and measured-vs-predicted collective byte reconciliation
(predictions from :mod:`horovod_tpu.telemetry.predict`).

The bubble helpers compare a *measured* pipeline idle fraction against
``parallel.pipeline``'s analytic schedules (gpipe ``2(S-1)/(2M+2(S-1))``,
lockstep/true 1F1B, interleaved ``2(S-1)/(2MV+2(S-1))`` straight from
``build_interleaved_schedule``) so a perf PR can show its bubble win as
a number instead of an equation.
"""

import time

from horovod_tpu.telemetry import core as _core

def _device_peak_flops():
    """Published bf16 peak of device 0 (``utils.devices``). An unknown
    ``device_kind`` — a CPU included — raises: pass ``peak_flops`` to
    :class:`StepTimer` for MFU against anything else."""
    import jax

    from horovod_tpu.utils.devices import (
        PEAK_BF16_FLOPS,
        match_device_table,
    )

    return match_device_table(jax.devices()[0], PEAK_BF16_FLOPS)


def compiled_flops(compiled):
    """Total FLOPs of one execution of a compiled jax program.

    ``compiled`` is the result of ``fn.lower(*args).compile()``;
    ``cost_analysis()`` returns a dict on current jax and a one-element
    list of dicts on older versions. Returns ``None`` when the backend
    does not report flops (some CPU paths).
    """
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = cost.get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


# ---- process-wide step-time EWMA --------------------------------------
# One number every observer agrees on: /healthz exports it and the
# autoscaler's step-time-trend signal reads it (docs/scale.md) — fed by
# whichever StepTimer instance is driving the training loop. EWMA so a
# single GC pause cannot flip a scaling decision on its own.
_STEP_EWMA_ALPHA = 0.1
_step_ewma_ms = 0.0


def _update_step_ewma(ms):
    global _step_ewma_ms
    _step_ewma_ms = (ms if _step_ewma_ms == 0.0 else
                     (1 - _STEP_EWMA_ALPHA) * _step_ewma_ms
                     + _STEP_EWMA_ALPHA * ms)


def step_time_ewma_ms():
    """The process's step-time EWMA in ms (0.0 until the first
    ``end_step``)."""
    return _step_ewma_ms


class StepTimer:
    """Accumulates per-step measurements; renders one summary row.

    Usage::

        timer = StepTimer(flops_per_step=..., predicted_bytes_per_step=...)
        for batch in data:
            with timer.step():
                loss, carry = step(carry, batch)
        row = timer.summary()

    ``block=True`` (default) blocks on the step outputs inside
    :meth:`end_step` so wall times mean what they say; pass ``False``
    when the surrounding harness already paces dispatch (then only the
    aggregate over many steps is meaningful).

    Collective bytes per step come from diffing the core metrics
    snapshot at step boundaries — zero instrumentation inside the step
    — and reconcile against ``predicted_bytes_per_step`` (from
    ``telemetry.predict``; the acceptance bar is 1%).
    """

    def __init__(self, flops_per_step=None, peak_flops=None,
                 predicted_bytes_per_step=None, block=True,
                 byte_op_classes=None):
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self.predicted_bytes_per_step = predicted_bytes_per_step
        self.block = block
        self.byte_op_classes = byte_op_classes
        self.step_times = []
        self.bytes_per_step = []
        # (tx, tx_logical) transport-byte deltas per step — diverge
        # only under wire compression (core.wire_bytes).
        self.wire_bytes_per_step = []
        # (intra_tx, intra_tx_logical, cross_tx, cross_tx_logical)
        # deltas per step: the per-plane split of the same transport
        # traffic (core.wire_plane_bytes) — cross is the DCN-priced
        # inter-slice hop of the hierarchical decomposition.
        self.plane_bytes_per_step = []
        # Per-step overlap ledger rows (docs/metrics.md "Overlap
        # ledger"): {plane: (exposed_us, hidden_us, total_us)} straight
        # from the core's interval-union math over the step window this
        # timer's own marks opened (exposed + hidden == total exactly).
        self.overlap_per_step = []
        self._step_id = None
        self._t0 = None
        self._bytes0 = None
        self._wire0 = None
        self._plane0 = None
        self._outputs = None

    # -- flops sources --------------------------------------------------

    def add_flops_from_compiled(self, compiled, calls=1):
        """Accumulate ``calls`` executions of a compiled program into
        ``flops_per_step`` (e.g. grad program x microbatches + apply)."""
        f = compiled_flops(compiled)
        if f is not None:
            self.flops_per_step = (self.flops_per_step or 0.0) + f * calls
        return f

    # -- per-step recording ---------------------------------------------

    def _read_bytes(self):
        # One snapshot serves the logical-payload, wire-vs-logical,
        # per-plane, and overlap-ledger reads alike.
        try:
            snap = _core.snapshot()
        except Exception:  # noqa: BLE001 — core not built/loaded: the
            return None, None, None, None  # timer still measures wall
        return (_core.total_collective_bytes(
                    snap, op_classes=self.byte_op_classes),
                _core.wire_bytes(snap),
                _core.wire_plane_bytes(snap),
                _core.wire_overlap(snap))

    def start_step(self):
        self._bytes0, self._wire0, self._plane0, _ = self._read_bytes()
        # Open the core-side step window (kStepBegin + overlap ledger,
        # docs/metrics.md "Step anatomy") AFTER the byte snapshot so
        # the window brackets exactly what this step moves.
        try:
            self._step_id = _core.step_mark(True, owner="StepTimer")
        except Exception:  # noqa: BLE001 — core not built/loaded
            self._step_id = None
        self._t0 = time.perf_counter()

    def end_step(self, outputs=None):
        if self._t0 is None:
            raise RuntimeError("end_step() without start_step()")
        if self.block and outputs is not None:
            try:
                import jax

                jax.block_until_ready(outputs)
            except Exception:  # noqa: BLE001 — non-jax outputs
                pass
        self.step_times.append(time.perf_counter() - self._t0)
        _update_step_ewma(self.step_times[-1] * 1000.0)
        # Close the window BEFORE the snapshot: the ledger folds the
        # step's wire spans on kStepEnd, so the read below sees this
        # step's union accounting in wire.overlap.*.last_*.
        if self._step_id is not None:
            # One owner per window: if another driver re-opened the
            # window mid-step (the fused optimizer's implicit boundary
            # racing this explicit scope), the ledger attribution below
            # would be a half-window masquerading as the full step —
            # refuse loudly instead of recording garbage.
            owner = _core.window_owner()
            if owner != "StepTimer":
                self._step_id = None
                self._t0 = None
                raise RuntimeError(
                    "StepTimer.end_step(): the step window this timer "
                    f"opened is now owned by {owner!r} — two step "
                    "drivers are marking boundaries in the same "
                    "iteration; scope the step with ONE of the "
                    "explicit StepTimer or the fused optimizer's "
                    "implicit boundary (docs/metrics.md)")
            try:
                _core.step_mark(False)
            except Exception:  # noqa: BLE001
                pass
        b1, w1, p1, ov = self._read_bytes()
        if self._bytes0 is not None and b1 is not None:
            self.bytes_per_step.append(b1 - self._bytes0)
        if self._wire0 is not None and w1 is not None:
            self.wire_bytes_per_step.append(
                (w1[0] - self._wire0[0], w1[1] - self._wire0[1]))
        if self._plane0 is not None and p1 is not None:
            self.plane_bytes_per_step.append(
                tuple(a - b for a, b in zip(p1, self._plane0)))
        if self._step_id is not None and ov:
            self.overlap_per_step.append({
                plane: (ov[plane]["last_exposed_us"],
                        ov[plane]["last_hidden_us"],
                        ov[plane]["last_total_us"])
                for plane in ("intra", "cross") if plane in ov})
        self._step_id = None
        self._t0 = None

    class _Step:
        def __init__(self, timer):
            self._timer = timer

        def __enter__(self):
            self._timer.start_step()
            return self._timer

        def __exit__(self, exc_type, exc, tb):
            if exc_type is None:
                self._timer.end_step(self._timer._outputs)
            self._timer._outputs = None
            return False

    def step(self):
        """Context manager timing one step. To block on the step's
        outputs, hand them over via :meth:`set_outputs` inside the
        ``with`` body (or call start/end explicitly)."""
        return StepTimer._Step(self)

    def set_outputs(self, outputs):
        self._outputs = outputs
        return outputs

    def wrap(self, step_fn):
        """Instrument ``step_fn(carry, batch) -> (loss, carry)``: every
        call is timed (and, with ``block=True``, synchronized)."""
        def timed_step(carry, batch):
            self.start_step()
            out = step_fn(carry, batch)
            self.end_step(out)
            return out

        return timed_step

    # -- aggregates ------------------------------------------------------

    @property
    def steps(self):
        return len(self.step_times)

    def mean_step_s(self, skip_first=True):
        """Mean step wall time; the first recorded step is dropped by
        default (it carries compilation)."""
        times = self.step_times
        if skip_first and len(times) > 1:
            times = times[1:]
        return sum(times) / len(times) if times else None

    def mfu(self, skip_first=True):
        dt = self.mean_step_s(skip_first)
        if not dt or not self.flops_per_step:
            return None
        peak = self.peak_flops or _device_peak_flops()
        return self.flops_per_step / dt / peak

    def measured_bytes_per_step(self, skip_first=True):
        vals = self.bytes_per_step
        if skip_first and len(vals) > 1:
            vals = vals[1:]
        return sum(vals) / len(vals) if vals else None

    def byte_reconciliation(self):
        """measured / predicted collective bytes per step (1.0 = the
        static predictor and the runtime counters agree)."""
        measured = self.measured_bytes_per_step()
        if not measured or not self.predicted_bytes_per_step:
            return None
        return measured / self.predicted_bytes_per_step

    def wire_goodput_gbps(self, skip_first=True):
        """Collective payload moved per second of step wall time, in
        GB/s — the goodput column (payload only: negotiation frames and
        protocol overhead excluded by construction). LOGICAL bytes by
        design: compression makes the wire cheaper, not the payload
        smaller — see :meth:`wire_compression_ratio` for the wire side."""
        dt = self.mean_step_s(skip_first)
        bytes_ = self.measured_bytes_per_step(skip_first)
        if not dt or bytes_ is None:
            return None
        return bytes_ / dt / 1e9

    def wire_compression_ratio(self, skip_first=True):
        """Transport bytes / full-width bytes over the recorded steps:
        1.0 uncompressed, ~0.5 with bf16-on-wire fp32 traffic (the
        wire-vs-logical reconciliation of ``docs/wire.md``). The first
        step is dropped by default, matching every other aggregate (its
        compile-time one-off traffic would dilute the quotient)."""
        vals = self.wire_bytes_per_step
        if skip_first and len(vals) > 1:
            vals = vals[1:]
        tx = sum(w[0] for w in vals)
        txl = sum(w[1] for w in vals)
        return tx / txl if txl else None

    def plane_wire_summary(self, skip_first=True):
        """Per-plane transport accounting over the recorded steps:
        ``{plane: {tx_bytes_per_step, goodput_gbps,
        compression_ratio}}`` for ``intra`` (ICI-priced/local hops) and
        ``cross`` (the DCN-priced inter-slice hop the hierarchical
        decomposition books separately). Per-plane compression is the
        point: ``HOROVOD_CROSS_PLANE_COMPRESSION`` moves only the cross
        ratio to ~0.5 while intra stays 1.0, and the two byte streams
        must sum exactly to the total wire counters (pinned in ``make
        reshard-smoke``). ``None`` when no plane deltas were recorded."""
        vals = self.plane_bytes_per_step
        if skip_first and len(vals) > 1:
            vals = vals[1:]
        if not vals:
            return None
        dt = self.mean_step_s(skip_first)
        n = len(vals)
        out = {}
        for plane, (itx, itxl) in (("intra", (0, 1)), ("cross", (2, 3))):
            tx = sum(v[itx] for v in vals)
            txl = sum(v[itxl] for v in vals)
            out[plane] = {
                "tx_bytes_per_step": tx / n,
                "goodput_gbps": (tx / n / dt / 1e9) if dt else None,
                "compression_ratio": (tx / txl) if txl else None,
            }
        return out

    def overlap_summary(self, skip_first=True):
        """Per-plane step-anatomy ledger over the recorded steps
        (docs/metrics.md "Overlap ledger"): ``{plane:
        {mean_exposed_wire_ms, mean_hidden_wire_ms,
        mean_total_wire_ms, overlap_efficiency}}`` plus a combined
        ``overlap_efficiency`` across planes. ``exposed`` is wire time
        that ran while an API thread sat blocked in ``synchronize``
        (the host had nothing to do but watch the wire); ``hidden =
        total - exposed`` is wire time that drained while the host
        kept computing or dispatching — the compute/collective overlap
        win the jit-lane fusion schedule moves (docs/fusion.md).
        exposed + hidden == total exactly, per step, by construction. The ``mean_`` prefix is deliberate: the
        snapshot's ``wire.overlap`` and ``/healthz`` expose CUMULATIVE
        ``exposed_wire_ms`` totals under the unprefixed names — the
        two shapes must not share a key. ``None`` until a step
        recorded ledger rows."""
        vals = self.overlap_per_step
        if skip_first and len(vals) > 1:
            vals = vals[1:]
        if not vals:
            return None
        n = len(vals)
        out = {}
        all_exp = all_tot = 0
        for plane in ("intra", "cross"):
            exp = sum(v[plane][0] for v in vals if plane in v)
            hid = sum(v[plane][1] for v in vals if plane in v)
            tot = sum(v[plane][2] for v in vals if plane in v)
            all_exp += exp
            all_tot += tot
            out[plane] = {
                "mean_exposed_wire_ms": exp / 1000.0 / n,
                "mean_hidden_wire_ms": hid / 1000.0 / n,
                "mean_total_wire_ms": tot / 1000.0 / n,
                "overlap_efficiency": (hid / tot) if tot else 0.0,
            }
        out["overlap_efficiency"] = (
            (all_tot - all_exp) / all_tot if all_tot else 0.0)
        return out

    def summary(self):
        """One JSON-ready row of everything the timer knows."""
        snap = None
        try:
            snap = _core.snapshot()
        except Exception:  # noqa: BLE001
            pass
        row = {
            "steps": self.steps,
            "mean_step_s": self.mean_step_s(),
            "mfu": self.mfu(),
            "flops_per_step": self.flops_per_step,
            "bytes_per_step": self.measured_bytes_per_step(),
            "predicted_bytes_per_step": self.predicted_bytes_per_step,
            "byte_reconciliation": self.byte_reconciliation(),
            "wire_goodput_gbps": self.wire_goodput_gbps(),
            "wire_compression_ratio": self.wire_compression_ratio(),
            "plane_wire": self.plane_wire_summary(),
            "overlap": self.overlap_summary(),
        }
        if snap and snap.get("initialized"):
            row["cache_hit_rate"] = snap["cache"]["hit_rate"]
            row["cycle_stalls"] = snap["cycle"]["stalls"]
        return row


# ---- pipeline bubble accounting ---------------------------------------


def analytic_bubble(schedule, S, M, num_virtual=1):
    """The schedule's predicted idle fraction, from the same closed
    forms / tables the engines execute (``parallel.pipeline``).
    Schedules:
    ``gpipe``, ``1f1b`` (lockstep), ``interleaved_1f1b``."""
    if schedule == "gpipe":
        return 2 * (S - 1) / (2 * M + 2 * (S - 1))
    if schedule == "1f1b":
        return 2 * (S - 1) / (M + 2 * (S - 1))
    if schedule == "interleaved_1f1b":
        from horovod_tpu.parallel.pipeline import build_interleaved_schedule

        return build_interleaved_schedule(S, num_virtual, M).bubble_fraction
    raise ValueError(f"unknown schedule {schedule!r}")


def measured_bubble(step_time_s, subtick_time_s, M, num_virtual=1):
    """Measured idle fraction: each device runs ``2*M*V`` useful
    fwd/bwd subticks per step, so work time is ``2*M*V*subtick`` and
    everything else in the step wall time is bubble (plus comms — on
    hardware, measure ``subtick_time_s`` by timing the stage program
    standalone)."""
    work = 2.0 * M * num_virtual * subtick_time_s
    if step_time_s <= 0:
        raise ValueError("step_time_s must be positive")
    return max(0.0, 1.0 - work / step_time_s)


def bubble_report(schedule, S, M, num_virtual, step_time_s,
                  subtick_time_s):
    """Measured vs analytic bubble for one pipeline configuration.

    ``excess`` is the gap the analytic model cannot explain —
    scheduling overhead, comms not overlapped, stragglers — i.e. the
    actionable number."""
    measured = measured_bubble(step_time_s, subtick_time_s, M,
                               num_virtual)
    analytic = analytic_bubble(schedule, S, M, num_virtual)
    return {
        "schedule": schedule, "S": S, "M": M, "V": num_virtual,
        "measured_bubble": round(measured, 4),
        "analytic_bubble": round(analytic, 4),
        "excess": round(measured - analytic, 4),
        "step_time_s": step_time_s,
        "subtick_time_s": subtick_time_s,
    }
