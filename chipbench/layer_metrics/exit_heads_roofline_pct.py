"""The share of the chip's peak of a looped decoder's exits: the least
time the chip could take for what a step REQUIRES of the R heads (the
model adapter's ``exit_heads_work``: ``chipbench/ouro_counts.py``, the
logits of every token at every exit and their two gradients over the
published bf16 peak; FLOPs bind) over the time
``exit_heads_ms_per_step`` reads, in percent. The blocks' recomputed
logits, the cross-entropies, the gates and the entropy lengthen the time
and are not credited. Cannot pass 100. ``None`` where the program has no
such scopes or the model kind counts no such work."""

from chipbench import ouro_counts
from chipbench.layer_metrics import exit_heads_ms_per_step


def read(ctx):
    ms = exit_heads_ms_per_step.read(ctx)
    work = getattr(ctx.model, "exit_heads_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = ouro_counts.floor_s(jax.local_devices()[0].device_kind, work())
    return 100.0 * floor / (ms / 1e3)
