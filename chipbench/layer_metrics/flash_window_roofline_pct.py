"""The windowed flash calls' share of their roofline: the least time the
chip could take for the work a step REQUIRES of the window layers'
attention (the model adapter's ``flash_window_work``:
``chipbench/afmoe_counts.py``, score and value products over the band's
visible pairs ``T*W - W*(W-1)/2``, forward once and backward twice that;
q, k, v, o and their gradients moved once; compute binds at the cell's
shape, 7.3 ms a layer against 1.1 ms of bytes) over the time
``flash_window_ms_per_step`` reads, in percent. Work a remat mode runs a
second time lengthens the time and is not credited; the masked half of a
tile an edge crosses is not required work either. Cannot pass 100.
``None`` where no flash call carries a window or the model kind counts
no such work."""

from chipbench import afmoe_counts
from chipbench.layer_metrics import flash_window_ms_per_step


def read(ctx):
    ms = flash_window_ms_per_step.read(ctx)
    work = getattr(ctx.model, "flash_window_work", None)
    if ms is None or work is None:
        return None
    import jax

    floor = afmoe_counts.floor_s(jax.local_devices()[0].device_kind,
                                 *work())
    return 100.0 * floor / (ms / 1e3)
