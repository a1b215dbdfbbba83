"""Device time of the elementwise chains round the gated delta rule per
traced step: every op under the scope ``gdn.chain`` (the depthwise
causal convolution over ``[q, k, v]`` and its SiLU, the L2 norms of
``q`` and ``k``, ``beta`` and ``g``, and behind the rule the gated
RMSNorm a head), all phases (``chipbench/scopes.py``). What the compiler
folds into the projections' matmuls counts with those
(``gdn_proj_ms_per_step``). ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "gdn.chain")
    except ValueError:       # a program from before the scope
        return None
