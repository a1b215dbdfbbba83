"""The SSD recurrence's share of its roofline: the least time the chip
could take for what a step REQUIRES of the recurrence (the model
adapter's ``ssd_work``: ``chipbench/ssd_counts.py``, the recurrence as it
is written, 4 x heads x channels x states FLOPs a token forward and twice
that backward; ``x``, ``dt``, ``B``, ``C``, ``y`` and their gradients
moved once; the larger of FLOPs over the published bf16 peak and bytes
over the published HBM bandwidth) over the time ``ssd_core_ms_per_step``
reads, in percent. The chunked form's extra products, the states kept at
chunk boundaries, a forward that a remat mode runs a second time and
what else runs under the scope (the running sum, the skip, the gates'
copies) lengthen the time and are not credited. Cannot pass 100.
``None`` where the program has no such scope or the model kind counts no
such work."""

from chipbench import ssd_counts
from chipbench.layer_metrics import ssd_core_ms_per_step


def read(ctx):
    ms = ssd_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "ssd_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = ssd_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
