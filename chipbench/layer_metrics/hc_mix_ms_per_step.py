"""Device time of the hyper-connections per traced step: every op under
the scope ``hc.mix`` (``models/llama.py:_hyper_connection``: the
streams' RMS, the three coefficient projections, the Sinkhorn
iterations, ``H_pre``'s sum, ``H_res``'s mix and ``H_post``'s add, the
copies in and the sum out), forward, recomputed and backward. Bytes, not
FLOPs: ``chipbench/mla_counts.py:hc_bytes`` is its floor
(``chipbench/scopes.py``). ``None`` for a program without the scope."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "hc.mix")
    except ValueError:       # a program from before the scope
        return None
