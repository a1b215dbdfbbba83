"""The grouped GEMMs' share of their roofline: the least time the chip
could take for the work a step REQUIRES of them (``chipbench/
moe_counts.py``: nine matmuls a layer of 2*M*K*N FLOPs each, operands
read and results written once; the larger of FLOPs over the published
bf16 peak and bytes over the published HBM bandwidth: compute binds at
OLMoE's shapes, at the cell's 2 layers 25.1 ms of FLOPs against 14.7 ms
of bytes) over the time
``moe_gmm_ms_per_step`` reads, in percent. Forward calls that a remat
mode runs a second time lengthen the time and are not credited, so the
share of a lean remat mode is lower than the kernels' own rate. Cannot
pass 100. ``None`` where there is no such kernel or the model kind
counts no such work."""

from chipbench import moe_counts
from chipbench.layer_metrics import moe_gmm_ms_per_step


def read(ctx):
    ms = moe_gmm_ms_per_step.read(ctx)
    work = getattr(ctx.model, "grouped_gemm_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor_s, _ = moe_counts.grouped_gemm_floor_s(
        jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor_s / (ms / 1e3)
