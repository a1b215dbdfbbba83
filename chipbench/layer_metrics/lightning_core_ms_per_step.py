"""Device time of the lightning layers' recurrence per traced step: every
op under the scope ``lightning.core`` (``ops/ssd.py`` as a
``lightning_attention`` layer calls it: the two kernels, ``hvd_ssd_fwd``
and ``hvd_ssd_bwd`` by their ``kernel_metadata``, at a head a group and
128 x 128 states, the running sum of the constant decay and the
lane-dense copies of the gates they read), forward, again where a remat
mode re-runs the layer, and backward (``chipbench/scopes.py``). ``None``
for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "lightning.core")
    except ValueError:       # a program from before the scope
        return None
