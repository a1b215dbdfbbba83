"""What a training step REQUIRES of the elementwise chain round the
gated delta rule (a Gated DeltaNet mixer's convolution, SiLU, unit
vectors and gates before the rule, its gated norm behind it), computed
from shapes: beside ``gdn_counts.py`` (the rule itself; not edited) and
kept with the benchmark for the same reason. The count reads the WORK,
not what implements it: every operand of the two stages read once and
every result written once, forward and backward. A padded tile, a
second pass over an array, a copy between two layouts, float32
temporaries through HBM or a forward that a remat mode runs a second
time lengthen the time and are not credited. The chain is elementwise
but for a reduction a head: a few FLOPs a byte, so bytes bind.

A token of a layer, ``kw = hk dk`` and ``vw = hv dv`` columns:

- stage one forward reads ``[q | k | v]`` as the projection leaves them
  (``2 kw + vw``) and writes them convolved, activated and normed (the
  same); backward reads them again and their three cotangents and
  writes the projection's;
- the gates: forward reads ``[b | a]`` (``2 hv``) and writes ``beta``
  and ``g`` (float32); backward reads ``[b | a]`` and the two
  cotangents and writes the projection's;
- stage two forward reads ``o`` and ``z`` (``2 vw``) and writes the
  gated norm (``vw``); backward reads ``o``, ``z`` and the cotangent and
  writes ``do`` and ``dz``.

The taps and the gains are read once a layer, not a token: left out.
"""

from chipbench import peaks


def chain_bytes(tokens, key_heads, value_heads, dk, dv, layers, itemsize=2):
    """Bytes the chains of ``layers`` delta-rule layers must move in one
    training step over ``tokens`` tokens."""
    x = 2 * key_heads * dk + value_heads * dv
    vw, gates = value_heads * dv, 2 * value_heads
    stage_one = (2 + 3) * x * itemsize
    gating = gates * (itemsize + 4) + gates * (itemsize + 4 + itemsize)
    stage_two = (3 + 5) * vw * itemsize
    return (stage_one + gating + stage_two) * tokens * layers


def floor_s(device_kind, nbytes):
    """The least time the chip could take: the bytes over the published
    HBM bandwidth."""
    return nbytes / peaks.peak(device_kind, "hbm_bytes_per_s")
