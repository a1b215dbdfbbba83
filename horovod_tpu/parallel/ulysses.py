"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

Net-new vs the reference (Horovod has no sequence parallelism —
SURVEY.md §5.7), complementing ring attention: instead of rotating K/V
around the ring, two ``all_to_all`` collectives re-shard
sequence-parallel Q/K/V from (tokens split, all heads) to (all tokens,
heads split), run ordinary full-sequence attention locally per head
group, and shard back. Communication is 2 all-to-alls of Q/K/V/O
instead of ``P`` neighbor exchanges of K/V — cheaper than the ring when
the per-device sequence is short relative to the head count, and it
reuses the single-device flash/blockwise kernel unchanged.

Trade-off vs ring attention: the mesh axis size must divide the head
count (grouped-query K/V heads are replicated up to lcm(Hkv, P) when
the axis does not divide Hkv), and peak activation memory holds the
full sequence for H/P heads.
"""

import math

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.ring_attention import (
    _repeat_kv,
    blockwise_attention,
)


def ulysses_attention(q, k, v, axis_name, causal=True, use_flash=None):
    """Exact attention with sequence sharded over mesh axis ``axis_name``.

    Must run inside shard_map with the sequence dimension sharded
    contiguously across the axis. Local shards: q [B, T/P, H, D];
    k, v [B, T/P, Hkv, D]. Requires H % P == 0; when P does not divide
    Hkv, K/V heads are replicated up to lcm(Hkv, P) first.

    ``use_flash`` (default: auto — True when the operands live on a
    TPU) runs the post-all-to-all local attention through the pallas
    flash kernels (which handle the remaining GQA grouping natively)
    instead of the XLA blockwise math.
    """
    if use_flash is None:
        from horovod_tpu.ops._platform import use_pallas

        use_flash = use_pallas("ulysses_attention", (q, k, v))
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention needs n_heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring_attention otherwise")
    if k.shape[2] % n != 0:
        # GQA head count not divisible by the axis: replicate K/V only up
        # to lcm(Hkv, P). Both Hkv and P divide H, so the lcm does too,
        # and the local attention re-expands the remaining grouping —
        # moving H/lcm× less K/V than replicating to H.
        target = k.shape[2] * n // math.gcd(k.shape[2], n)
        k = _repeat_kv(k, target // k.shape[2])
        v = _repeat_kv(v, target // v.shape[2])

    def to_heads(x):  # [B, T/P, H', D] -> [B, T, H'/P, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)
    if use_flash:
        from horovod_tpu.ops import flash_attention

        out = flash_attention(qg, kg, vg, causal=causal)
    else:
        out = blockwise_attention(qg, kg, vg, causal=causal)
    # [B, T, H/P, D] -> [B, T/P, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_self_attention(q, k, v, mesh, causal=True, batch_axis="data",
                           seq_axis="seq"):
    """User-facing wrapper: shard q/k/v over (batch, seq) and run
    ulysses_attention under shard_map on the given mesh."""
    spec = P(batch_axis, seq_axis, None, None)

    @jax.shard_map(mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)
    def _run(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, seq_axis, causal=causal)

    return _run(q, k, v)
