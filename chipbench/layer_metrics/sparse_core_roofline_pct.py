"""The sparse core's share of its roofline: the least time the chip
could take for what a step REQUIRES of the attention over the chosen
blocks (the model adapter's ``sparse_work``: ``chipbench/sala_counts.py``,
every token ``min(begun, topk)`` blocks with its own to the causal edge,
4 x head_dim FLOPs a pair and head forward and twice that backward;
``q``, ``k``, ``v``, ``o`` and their gradients moved once; the larger of
FLOPs over the published bf16 peak and bytes over the published HBM
bandwidth) over the time ``sparse_core_ms_per_step`` reads, in percent.
Masked-out work in a visited tile, the blocks a tile visits beyond what
a row chose (the UNION of its tokens' sets), a forward that a remat mode
runs a second time and the copies under the scope lengthen the time and
are not credited. Cannot pass 100. ``None`` where the program has no such
scope or the model kind counts no such work."""

from chipbench import sala_counts
from chipbench.layer_metrics import sparse_core_ms_per_step


def read(ctx):
    ms = sparse_core_ms_per_step.read(ctx)
    work = getattr(ctx.model, "sparse_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = sala_counts.floor_s(jax.local_devices()[0].device_kind, *work())
    return 100.0 * floor / (ms / 1e3)
