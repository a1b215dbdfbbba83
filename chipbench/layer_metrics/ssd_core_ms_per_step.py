"""Device time of the SSD recurrence per traced step: every op under the
scope ``ssd.core`` (``ops/ssd.py`` as a ``mamba2`` layer calls it: the
two kernels, ``hvd_ssd_fwd`` and ``hvd_ssd_bwd`` by their
``kernel_metadata``, the running sum of ``dt A``, the lane-dense copies
of the gates they read, the skip ``D x``, the sums that fold a group's
partial gradients), forward, again where a remat mode re-runs the layer,
and backward (``chipbench/scopes.py``). ``None`` for a program without
the scope (one from before it, or a model with no such layer)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssd.core")
    except ValueError:       # a program from before the scope
        return None
