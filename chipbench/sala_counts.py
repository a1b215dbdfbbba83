"""What a training step REQUIRES of MiniCPM-SALA's two mixers, computed
from shapes: beside ``peaks.py``, ``ssd_counts.py`` and the other count
files (none edited) and kept with the benchmark for the same reason. The
counts read the MATHEMATICS, not what implements it, so a later kernel PR
cannot make them stale.

The sparse core (InfLLM-V2's attention over the chosen blocks), a query
head: a token ``t`` attends ``min(begun blocks, topk)`` blocks of
``block`` keys, its own block to the causal edge (``t mod block + 1``
keys) and every other whole (they lie before it): that many (query, key)
PAIRS, from ``T`` and the selection's sizes alone, whatever was chosen. A
pair costs ``q . k`` and ``p v``, ``4 d`` FLOPs a head forward, and twice
that backward (``dp``, ``dv``, ``dq``, ``dk``: each product transposed
once for either factor). ``q``, ``k``, ``v``, ``o`` and their gradients
move once. NOT credited: masked-out work in a visited tile (a tile of
neighbouring tokens visits the UNION of its tokens' blocks), a forward
that a remat mode runs a second time, the selection itself (its scope is
``sparse.select``, its metric a time).

The lightning core is the SSD recurrence with a state of ``d x d`` a
head: ``ssd_counts.core_flops`` / ``core_bytes`` called, not copied,
less the ``dt`` bytes (Lightning Attention has no step size: the
architecture computes none).
"""

from chipbench import peaks, ssd_counts


def sparse_pairs(tokens, block, topk):
    """(query, key) pairs a query head of one sequence of ``tokens``
    attends: every token ``min(begun, topk)`` blocks, its own to the
    causal edge."""
    pairs = 0
    for first in range(0, tokens, block):        # the tokens of a block
        n = min(block, tokens - first)
        chosen = min(first // block + 1, topk)
        pairs += n * (chosen - 1) * block + n * (n + 1) // 2
    return pairs


def sparse_core_flops(batch, tokens, heads, head_dim, block, topk, layers):
    """Required FLOPs of ``layers`` sparse cores in one training step
    over ``batch`` sequences of ``tokens``: ``4 d`` a pair and head
    forward, twice that backward."""
    return 3 * 4 * head_dim * heads * batch \
        * sparse_pairs(tokens, block, topk) * layers


def sparse_core_bytes(batch, tokens, heads, kv_heads, head_dim, layers,
                      itemsize=2):
    """Bytes those layers must move if every operand is read and every
    result written once: forward reads ``q``, ``k``, ``v`` and writes
    ``o``; backward reads them, ``o`` and ``do`` and writes ``dq``,
    ``dk``, ``dv``. The table of chosen blocks is the selection's."""
    q, kv = heads * head_dim * itemsize, 2 * kv_heads * head_dim * itemsize
    forward = q + kv + q
    backward = (q + kv + q) + q + (q + kv)
    return (forward + backward) * batch * tokens * layers


def lightning_core_flops(tokens, heads, head_dim, layers):
    return ssd_counts.core_flops(tokens, heads, head_dim, head_dim, heads,
                                 layers)


def lightning_core_bytes(tokens, heads, head_dim, layers, itemsize=2):
    """``ssd_counts.core_bytes`` with a group a head, less the ``dt``
    bytes: float32 a head and token read forward and backward and its
    gradient written."""
    return ssd_counts.core_bytes(tokens, heads, head_dim, head_dim, heads,
                                 layers, itemsize) \
        - 3 * heads * 4 * tokens * layers


def floor_s(device_kind, flops, nbytes):
    """The least time the chip could take: the larger of FLOPs over the
    published bf16 peak and bytes over the published HBM bandwidth."""
    return max(flops / peaks.peak(device_kind),
               nbytes / peaks.peak(device_kind, "hbm_bytes_per_s"))
