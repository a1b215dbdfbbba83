"""Device time of the elementwise chains round the selective scan per
traced step: every op under the scope ``ssm.chain`` (the depthwise
causal convolution with its bias and SiLU, the three RMSNorms of the
step size's, ``B``'s and ``C``'s projections, the softplus, ``-exp`` of
the decay rates, and behind the scan the gate ``y * SiLU(z)``), all
phases (``chipbench/scopes.py``). What the compiler folds into the
projections' matmuls counts with those (``ssm_proj_ms_per_step``).
``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "ssm.chain")
    except ValueError:       # a program from before the scope
        return None
