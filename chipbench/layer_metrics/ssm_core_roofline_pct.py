"""The selective scan's share of its roofline: the least time the chip
could take for what a step REQUIRES of the scan (the model adapter's
``selective_scan_work``: ``chipbench/ssm_counts.py``, the recurrence as
it is written, 7 x channels x states FLOPs a token forward and twice
that backward; ``u``, ``dt``, ``B``, ``C``, ``y`` and their gradients
moved once; the larger of FLOPs over the published bf16 peak and bytes
over the published HBM bandwidth: bytes bind at the cell's shape, 1.13
ms a layer against 0.07 ms of FLOPs) over the time
``ssm_core_ms_per_step`` reads, in percent. The work is elementwise, on
the VPU and the EUP, which have no published peak: against the MXU's
FLOPs and the HBM's bytes the share is small by construction, and what
moves it is the kernels' time. A forward that a remat mode runs a second
time and the chunk's states the backward kernel computes again lengthen
the time and are not credited. Cannot pass 100. ``None`` where the
program has no such scope or the model kind counts no such work."""

from chipbench import ssm_counts
from chipbench.layer_metrics import ssm_core_ms_per_step


def read(ctx):
    ms = ssm_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "selective_scan_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = ssm_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
