"""Ring attention: exact long-context attention over a sharded sequence.

Net-new vs the reference (Horovod has no sequence parallelism —
SURVEY.md §5.7). The sequence axis of Q/K/V is sharded across the ``seq``
mesh axis; each step every device computes flash-style blockwise attention
against the K/V shard it currently holds, then rotates K/V one hop around
the ICI ring (``ppermute``). After ``seq_size`` steps every query has seen
every key exactly once; the online-softmax accumulators make the result
exact, not approximate. Communication per step is one neighbor exchange
that XLA overlaps with the attention matmuls.

Causal masking uses global positions, so fully-masked (future) blocks
contribute nothing and early-exit naturally via zeroed partial sums.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG_BIG = -1e30  # finite stand-in for -inf: keeps exp() NaN-free


def _repeat_kv(x, n_rep):
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] for grouped-query attention."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def _attn_block(q, k, v, q_pos, kv_pos, causal, scale, window=0):
    """One flash-attention block: returns unnormalized (o, m, l) stats.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; positions are global indices.
    o is f32 [B, Tq, H, D]; m (running max) and l (sum of exp) are
    f32 [B, H, Tq]. ``window`` W > 0 (causal only): key j is visible to
    row i where ``i - W < j <= i``.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        visible = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        if window:
            visible &= kv_pos[None, None, None, :] \
                > q_pos[None, None, :, None] - window
        s = jnp.where(visible, s, _NEG_BIG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(visible, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def _combine(o, m, l, o_blk, m_blk, l_blk):
    """Merge a new block into running online-softmax accumulators."""
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m_blk - m_new)
    l_new = alpha * l + beta * l_blk
    # [B, H, Tq] -> [B, Tq, H, 1] to scale o.
    def bcast(x):
        return jnp.transpose(x, (0, 2, 1))[..., None]
    o_new = bcast(alpha) * o + bcast(beta) * o_blk
    return o_new, m_new, l_new


def blockwise_attention(q, k, v, causal=True, q_offset=0, kv_offset=0,
                        window=0, scale=None):
    """Plain (single-device) attention with global-position causal mask.

    q: [B, Tq, H, D]; k, v: [B, Tk, Hkv, D]. The offsets give the global
    index of the first q/kv position (used by ring steps and by decode).
    ``window`` W > 0: sliding-window attention, each row sees its last W
    keys (itself included); what ``ops.flash_attention(window=W)`` runs
    off the TPU. ``v`` may be another width than ``q`` and ``k``;
    ``scale`` multiplies the scores (None: ``1 / sqrt(D)``).
    """
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    q_pos = q_offset + jnp.arange(q.shape[1])
    kv_pos = kv_offset + jnp.arange(k.shape[1])
    o, m, l = _attn_block(q, k, v, q_pos, kv_pos, causal, scale, window)
    l = jnp.maximum(l, 1e-30)
    out = o / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal):
    """Ring attention with the pallas flash kernels doing each step.

    Every chunk step is one fused kernel call (dynamic global-position
    offsets ride in SMEM, so ONE compiled kernel serves all steps);
    partial results merge by logsumexp, the exact online-softmax
    combination. K/V rotate UNREPEATED (GQA: n_rep× less ICI traffic
    than repeating before the ring). Differentiable end-to-end — the
    kernel's custom VJP folds the lse cotangent into its backward.
    """
    from horovod_tpu.ops.flash_attention import flash_attention_chunk

    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # Kernel layout [B, H, T, D]; stay there across steps (one
    # transpose in, one out — not per step).
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    o = jnp.zeros((b, h, tq, d), jnp.float32)
    lse = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    for step in range(n):
        src = (idx - step) % n  # whose shard we currently hold
        o_blk, lse_blk = flash_attention_chunk(
            qt, kt, vt, idx * tq, src * tk, causal=causal)
        new_lse = jnp.logaddexp(lse, lse_blk)
        o = (jnp.exp(lse - new_lse)[..., None] * o
             + jnp.exp(lse_blk - new_lse)[..., None]
             * o_blk.astype(jnp.float32))
        lse = new_lse
        if step != n - 1:
            perm = [(i, (i + 1) % n) for i in range(n)]
            kt = lax.ppermute(kt, axis_name, perm)
            vt = lax.ppermute(vt, axis_name, perm)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q, k, v, axis_name, causal=True, use_flash=None):
    """Exact attention with sequence sharded over mesh axis ``axis_name``.

    Must run inside shard_map (or pmap) with the sequence dimension of
    q/k/v sharded contiguously across the axis. Shapes are the LOCAL
    shards: q [B, Tq, H, D]; k, v [B, Tk, Hkv, D].

    ``use_flash`` (default: auto — True when the operands live on a
    TPU) runs every ring step through the pallas flash kernels instead
    of the XLA blockwise math (~2× at model shapes, and K/V rotate
    unrepeated under GQA).
    """
    if use_flash is None:
        from horovod_tpu.ops._platform import use_pallas

        use_flash = use_pallas("ring_attention", (q, k, v))
    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    b, tq, h, d = q.shape
    tk = k.shape[1]
    q_pos = idx * tq + jnp.arange(tq)

    o = jnp.zeros((b, tq, h, d), jnp.float32)
    m = jnp.full((b, h, tq), _NEG_BIG, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)

    # Static python loop: n is the (compile-time) mesh axis size. Each
    # iteration's ppermute is independent of the block matmul before it,
    # so XLA overlaps communication with compute.
    for step in range(n):
        src = (idx - step) % n  # whose shard we currently hold
        kv_pos = src * tk + jnp.arange(tk)
        o_blk, m_blk, l_blk = _attn_block(q, k, v, q_pos, kv_pos, causal,
                                          scale)
        o, m, l = _combine(o, m, l, o_blk, m_blk, l_blk)
        if step != n - 1:
            perm = [(i, (i + 1) % n) for i in range(n)]
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    l = jnp.maximum(l, 1e-30)
    out = o / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh, causal=True, batch_axis="data",
                        seq_axis="seq"):
    """User-facing wrapper: shard q/k/v over (batch, seq) and run
    ring_attention under shard_map on the given mesh."""
    spec = P(batch_axis, seq_axis, None, None)

    @jax.shard_map(mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)
    def _run(ql, kl, vl):
        return ring_attention(ql, kl, vl, seq_axis, causal=causal)

    return _run(q, k, v)
