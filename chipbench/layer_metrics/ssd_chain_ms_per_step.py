"""Device time of the elementwise chains round the SSD recurrence per
traced step: every op under the scope ``ssd.chain`` (the depthwise causal
convolution with its bias and SiLU over ``[X, B, C]``, the softplus of
the step sizes, ``-exp`` of the decay rates, and behind the recurrence
the gate ``y * SiLU(z)`` and the RMSNorm over each group's channels), all
phases (``chipbench/scopes.py``). What the compiler folds into the
projections' matmuls counts with those (``ssd_proj_ms_per_step``).
``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssd.chain")
    except ValueError:       # a program from before the scope
        return None
