"""What a training step REQUIRES of latent attention's core and of the
hyper-connections, computed from shapes: beside ``peaks.py``,
``sala_counts.py`` and the other count files (none edited) and kept with
the benchmark for the same reason. The counts read the MATHEMATICS, not
what implements it, so a later kernel PR cannot make them stale.

The core (``softmax(q k^T s, causal) v`` with queries and keys ``dqk``
wide beside values ``dv`` wide), a query head: a token attends itself and
every earlier one, ``T (T + 1) / 2`` (query, key) PAIRS a sequence. A
pair costs ``q . k`` and ``p v``, ``2 (dqk + dv)`` FLOPs a head forward,
and twice that backward (``dp``, ``dv``, ``dq``, ``dk``: each product
transposed once for either factor). ``q``, ``k``, ``v``, ``o`` and their
gradients move once: the training form builds a key and a value A HEAD
from the latent, so ``k`` and ``v`` are as many heads as ``q``. NOT
credited: masked-out work in a tile the diagonal crosses, a forward that
a remat mode runs a second time, the assembly of ``q`` and ``k`` from
their slices.

The hyper-connections are bytes, not FLOPs. One part (a mixer or a
feed-forward part) a token, ``n`` streams of ``d``: forward reads the
streams and writes them (``2 n d``), writes what the part reads and reads
what it returns (``2 d``); backward reads the streams and the cotangent
of the new ones, writes the cotangent of the old (``3 n d``), and moves
the part's input's and output's cotangents and its output again (``3
d``). The 24 coefficients a token and the 4 x 4 iterations are noise
beside them. NOT credited: the recomputed forward, float32 copies.
"""

from chipbench.sala_counts import floor_s  # noqa: F401  (the same floor)


def core_pairs(tokens):
    """(query, key) pairs a query head of one causal sequence attends."""
    return tokens * (tokens + 1) // 2


def core_flops(batch, tokens, heads, dqk, dv, layers):
    """Required FLOPs of ``layers`` latent-attention cores in one
    training step over ``batch`` sequences of ``tokens``: ``2 (dqk +
    dv)`` a pair and head forward, twice that backward."""
    return 3 * 2 * (dqk + dv) * heads * batch * core_pairs(tokens) * layers


def core_bytes(batch, tokens, heads, dqk, dv, layers, itemsize=2):
    """Bytes those layers must move if every operand is read and every
    result written once: forward reads ``q``, ``k``, ``v`` and writes
    ``o``; backward reads them, ``o`` and ``do`` and writes ``dq``,
    ``dk``, ``dv``."""
    qk, vo = 2 * dqk, 2 * dv
    forward = qk + vo
    backward = (qk + dv) + vo + (qk + dv)
    return (forward + backward) * heads * itemsize * batch * tokens * layers


def hc_bytes(tokens, streams, d_model, parts, itemsize=2):
    """Bytes ``parts`` hyper-connection parts must move in one training
    step over ``tokens`` tokens (see above): ``(5 n + 5) d`` values a
    token and part."""
    return (5 * streams + 5) * d_model * itemsize * tokens * parts
