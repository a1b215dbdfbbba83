"""What a training step REQUIRES of the gated delta rule (Gated
DeltaNet's recurrence), computed from shapes: beside ``peaks.py``,
``moe_counts.py`` and ``afmoe_counts.py`` (none edited) and kept with
the benchmark for the same reason. The count reads the RULE, not what
implements it: a chunked form spends more FLOPs (the WY factors, the
in-chunk scores) and moves more bytes (its chunk-major operands, the
chunk-boundary states), and none of that is credited; nor is a forward
that a remat mode runs a second time.

The rule, a value head and token, on a state ``S`` [dk, dv]::

    S <- exp(g) S              dk*dv      multiplies
    r  = v - S^T k             2*dk*dv    (a product of S with a vector)
    S <- S + k (beta r)^T      2*dk*dv    (a rank-one update)
    o  = S^T q                 2*dk*dv    (a product of S with a vector)

7*dk*dv FLOPs forward (the 2*dv of ``v - .`` and ``beta r`` are left
out); the backward pass needs twice the forward again (a cotangent of
the state carried back, each product transposed once for its vector and
once for the state), as for every matmul of ``peaks.py``.
"""

from chipbench import peaks


def rule_flops(tokens, value_heads, dk, dv, layers):
    """Required FLOPs of ``layers`` delta-rule layers in one training
    step over ``tokens`` tokens: forward once, backward twice that."""
    return 3 * 7 * dk * dv * value_heads * tokens * layers


def rule_bytes(tokens, key_heads, value_heads, dk, dv, layers, itemsize=2):
    """Bytes those layers must move if every operand is read and every
    result written once: forward reads ``q``, ``k`` (a key head's, read
    once for the value heads it serves), ``v`` and the two gates ``g``,
    ``beta`` (float32, one a value head) and writes ``o``; backward
    reads them and ``do`` and writes the five gradients. The state never
    leaves the chip."""
    qk, v = 2 * key_heads * dk, value_heads * dv
    gates = 2 * value_heads * 4
    forward = (qk + v + v) * itemsize + gates
    backward = (qk + v + v) * itemsize + gates \
        + (qk + v) * itemsize + gates
    return (forward + backward) * tokens * layers


def floor_s(device_kind, flops, nbytes):
    """The least time the chip could take: the larger of FLOPs over the
    published bf16 peak and bytes over the published HBM bandwidth."""
    return max(flops / peaks.peak(device_kind),
               nbytes / peaks.peak(device_kind, "hbm_bytes_per_s"))
