"""Latent attention's core's share of its roofline: the least time the
chip could take for what a step REQUIRES of ``softmax(q k^T s, causal)
v`` at 192 / 128 (the model adapter's ``mla_work``:
``chipbench/mla_counts.py``, ``T (T + 1) / 2`` pairs a head, ``2 (dqk +
dv)`` FLOPs a pair and head forward and twice that backward; ``q``,
``k``, ``v``, ``o`` and their gradients moved once; the larger of FLOPs
over the published bf16 peak and bytes over the published HBM bandwidth)
over the time ``mla_core_ms_per_step`` reads, in percent. Masked-out
work in a tile the diagonal crosses, a forward that a remat mode runs a
second time and the assembly and transposes under the scope lengthen the
time and are not credited. Cannot pass 100. ``None`` where the program
has no such scope or the model kind counts no such work."""

from chipbench import mla_counts
from chipbench.layer_metrics import mla_core_ms_per_step


def read(ctx):
    ms = mla_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "mla_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = mla_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
