"""Device time of the selective scan per traced step: every op under the
scope ``ssm.core`` (``ops/selective_scan.py`` as a ``mamba`` layer calls
it: the two kernels, ``hvd_ssm_scan_fwd`` and ``hvd_ssm_scan_bwd`` by
their ``kernel_metadata``, the lane-dense copies of a token's ``B`` and
``C`` they read, the sums that fold their partial gradients), forward,
again where a remat mode re-runs the layer, and backward
(``chipbench/scopes.py``). ``None`` for a program without the scope (one
from before it, or a model with no such layer)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssm.core")
    except ValueError:       # a program from before the scope
        return None
