"""The lightning recurrence's share of its roofline: the least time the
chip could take for what a step REQUIRES of it (the model adapter's
``lightning_work``: ``chipbench/sala_counts.py`` calling
``ssd_counts.py``, 4 x heads x 128 x 128 FLOPs a token forward and twice
that backward; ``q``, ``k``, ``v``, ``o`` and their gradients moved once,
no step size; the larger of FLOPs over the published bf16 peak and bytes
over the published HBM bandwidth) over the time
``lightning_core_ms_per_step`` reads, in percent. The chunked form's
extra products, the states kept at chunk boundaries, a forward that a
remat mode runs a second time and what else runs under the scope
lengthen the time and are not credited. Cannot pass 100. ``None`` where
the program has no such scope or the model kind counts no such work."""

from chipbench import sala_counts
from chipbench.layer_metrics import lightning_core_ms_per_step


def read(ctx):
    ms = lightning_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "lightning_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = sala_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
