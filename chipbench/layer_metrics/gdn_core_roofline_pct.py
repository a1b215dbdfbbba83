"""The gated delta rule's share of its roofline: the least time the chip
could take for what a step REQUIRES of the rule (the model adapter's
``gated_delta_rule_work``: ``chipbench/gdn_counts.py``, the recurrence
as it is written, 7 x dk x dv FLOPs a token and value head forward and
twice that backward; ``q``, ``k``, ``v``, the gates, ``o`` and their
gradients moved once; the larger of FLOPs over the published bf16 peak
and bytes over the published HBM bandwidth: bytes bind at the cell's
shape, 1.33 ms a layer against 0.92 ms of FLOPs) over the time
``gdn_core_ms_per_step`` reads, in percent. What a chunked form spends
beyond the rule (the WY factors, the in-chunk scores, chunk-major
copies, the chunk-boundary states through HBM) and a forward that a
remat mode runs a second time lengthen the time and are not credited.
Cannot pass 100. ``None`` where the program has no such scope or the
model kind counts no such work."""

from chipbench import gdn_counts
from chipbench.layer_metrics import gdn_core_ms_per_step


def read(ctx):
    ms = gdn_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "gated_delta_rule_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = gdn_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
