"""What a training step REQUIRES of the selective scan (a Mamba-1
state-space layer's recurrence), computed from shapes: beside
``peaks.py``, ``moe_counts.py``, ``afmoe_counts.py`` and
``gdn_counts.py`` (none edited) and kept with the benchmark for the same
reason. The count reads the RECURRENCE, not what implements it: a kernel
that re-reads ``B_t`` and ``C_t`` lane-dense, keeps chunk-boundary
states or runs a chunk's states a second time in the backward pass
spends more, and none of that is credited; nor is a forward that a remat
mode runs a second time.

The recurrence, a channel, state and token::

    a  = exp(dt A)             (the exponential is not counted)
    s <- a s + (dt u) B        1 + 2 + 1 = 4    (decay's multiply, two
                                                 for dt u B, the add)
    y += s C                   2                (a multiply-add)

with ``dt A`` itself one multiply more: 7 FLOPs forward; the backward
pass needs twice the forward again (the state's cotangent carried back,
each product transposed once for either factor), as for every matmul of
``peaks.py``.
"""

from chipbench import peaks


def scan_flops(tokens, channels, states, layers):
    """Required FLOPs of ``layers`` selective scans in one training step
    over ``tokens`` tokens: forward once, backward twice that."""
    return 3 * 7 * channels * states * tokens * layers


def scan_bytes(tokens, channels, states, layers, itemsize=2):
    """Bytes those layers must move if every operand is read and every
    result written once: forward reads ``u`` (the compute dtype), ``dt``
    (float32, as the architecture computes it), a token's ``B`` and
    ``C`` and writes ``y``; backward reads them and ``dy`` and writes
    the gradients of ``u``, ``dt``, ``B`` and ``C``. ``A`` and ``D`` and
    their gradients are one number a (channel, state) pair a layer:
    nothing. The state never leaves the chip."""
    wide, step, maps = channels * itemsize, channels * 4, 2 * states * itemsize
    forward = wide + step + maps + wide
    backward = (wide + step + maps) + wide + (wide + step + maps)
    return (forward + backward) * tokens * layers


def floor_s(device_kind, flops, nbytes):
    """The least time the chip could take: the larger of FLOPs over the
    published bf16 peak and bytes over the published HBM bandwidth."""
    return max(flops / peaks.peak(device_kind),
               nbytes / peaks.peak(device_kind, "hbm_bytes_per_s"))
