"""The share of its roofline of the elementwise chain round the gated
delta rule: the least time the chip could take for what a step REQUIRES
of the chain (the model adapter's ``gdn_chain_work``:
``chipbench/gdn_chain_counts.py``, every operand of the two stages read
once and every result written once, forward and backward, over the
published HBM bandwidth; bytes bind) over the time
``gdn_chain_ms_per_step`` reads, in percent. A padded tile, a second
pass, a copy between layouts or a forward that a remat mode runs a
second time lengthen the time and are not credited. Cannot pass 100.
``None`` where the program has no such scope or the model kind counts
no such work."""

from chipbench import gdn_chain_counts
from chipbench.layer_metrics import gdn_chain_ms_per_step


def read(ctx):
    ms = gdn_chain_ms_per_step.read(ctx)
    work = getattr(ctx.model, "gdn_chain_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = gdn_chain_counts.floor_s(
        jax.local_devices()[0].device_kind, work())
    return 100.0 * floor / (ms / 1e3)
