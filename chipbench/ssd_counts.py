"""What a training step REQUIRES of the SSD recurrence (a Mamba-2
state-space layer's), computed from shapes: beside ``peaks.py``,
``moe_counts.py``, ``afmoe_counts.py``, ``gdn_counts.py`` and
``ssm_counts.py`` (none edited) and kept with the benchmark for the same
reason. The count reads the RECURRENCE, not what implements it: the
chunked form's extra products (``C B^T``, the decayed scores, the
intra-chunk matmul), the states kept at chunk boundaries and a forward
that a remat mode runs a second time spend more, and none of that is
credited.

The recurrence, a head of ``P`` channels and ``N`` states and a token::

    S <- a S + (dt x) (x) B     the rank-one update's multiply-add: 2 P N
                                (the decay is ONE scalar a head: in the
                                matmul form it scales scores and
                                outputs, never the P N entries of S)
    y  = S C                    a multiply-add: 2 P N

``4 P N`` FLOPs a head and token forward (``dt x`` and ``dt A`` are one
multiply a channel and a head more: nothing beside ``P N``); the backward
pass needs twice the forward again (the state's cotangent carried back,
each product transposed once for either factor), as for every matmul of
``peaks.py``.
"""

from chipbench import peaks


def core_flops(tokens, heads, head_dim, states, groups, layers):
    """Required FLOPs of ``layers`` SSD recurrences in one training step
    over ``tokens`` tokens: forward once, backward twice that.
    (``groups`` share ``B`` and ``C``: fewer bytes, the same FLOPs.)"""
    del groups
    return 3 * 4 * heads * head_dim * states * tokens * layers


def core_bytes(tokens, heads, head_dim, states, groups, layers, itemsize=2):
    """Bytes those layers must move if every operand is read and every
    result written once: forward reads ``x`` (the compute dtype), ``dt``
    (float32 a head, as the architecture computes it), a token's ``B``
    and ``C`` a group and writes ``y``; backward reads them and ``dy``
    and writes the gradients of ``x``, ``dt``, ``B`` and ``C``. ``A`` and
    ``D`` and their gradients are one number a head a layer: nothing.
    The state never leaves the chip."""
    wide = heads * head_dim * itemsize
    step, maps = heads * 4, 2 * groups * states * itemsize
    forward = wide + step + maps + wide
    backward = (wide + step + maps) + wide + (wide + step + maps)
    return (forward + backward) * tokens * layers


def floor_s(device_kind, flops, nbytes):
    """The least time the chip could take: the larger of FLOPs over the
    published bf16 peak and bytes over the published HBM bandwidth."""
    return max(flops / peaks.peak(device_kind),
               nbytes / peaks.peak(device_kind, "hbm_bytes_per_s"))
