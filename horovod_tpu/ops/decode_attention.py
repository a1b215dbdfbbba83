"""Fused single-position decode attention as a pallas TPU kernel.

The XLA path (models/generate.py's einsum chain) materializes the f32
score tensor [B, G, R, S], the softmax statistics, and the f32->bf16
probability cast as separate HBM round-trips — ~0.07 ms/layer of pure
bandwidth overhead on top of the KV-cache stream at flagship batch 64.
This kernel folds scores + masked softmax + the value contraction into
the one pass that streams the cache: grid (batch, kv-head group), each
program loads its [S, D] K/V slices into VMEM (decode caches are
short — S = prompt + max_new), computes the R grouped query rows
against them, and writes [R, D] back. GQA-native like the rest of the
stack: K/V are read at their stored head count.

Same numeric recipe as the XLA path and the training flash kernel:
f32 scores and softmax, bf16 probabilities into a f32-accumulated PV.
Operands off-TPU take the einsum path (``ops._platform.use_pallas``
decides from the operands' device); interpret mode gives the kernel CPU
test coverage (tests/single/test_decode_attention.py).

Reference analog: none (Horovod ships no inference path).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas

_NEG = -1e30

# Scoped-VMEM budget one grid program's resident slices may take.
_VMEM_BUDGET_BYTES = 12 * (1 << 20)

# Tests set this to run the kernel in interpret mode on CPU.
_INTERPRET = False


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, scale):
    # q [R, D]; k/v [S, D] — one (batch, kv-head) slice, fully resident
    # in VMEM (decode S is prompt+max_new, ~hundreds). pos is an SMEM
    # scalar: cache slots <= pos are valid.
    q = q_ref[:, :]
    k = k_ref[:, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = lax.broadcasted_iota(jnp.int32, s.shape, 1) <= pos_ref[0]
    s = jnp.where(valid, s, _NEG)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=1, keepdims=True)
    p = (p / l).astype(v_ref.dtype)
    o_ref[:, :] = jax.lax.dot_general(
        p, v_ref[:, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def kernel_fits_vmem(q_shape, cache_shape, cache_dtype):
    """Whether the fused kernel can lower at these (static) shapes.

    Each grid program holds its whole [S, D] K and V slices plus the
    f32 score rows in VMEM; past ~long-context cache lengths that
    exceeds the ~16 MB scoped budget and the kernel cannot lower, so
    :func:`decode_attention` takes the same-recipe einsum chain there
    (slower per step, any S). Public so callers (``chip_smoke.py``) can
    print which side of the gate a shape lands on."""
    _, _, hq, d = q_shape
    hkv, s_len = cache_shape[1], cache_shape[2]
    n_rep = hq // hkv
    vmem_bytes = (2 * s_len * d * jnp.dtype(cache_dtype).itemsize  # K+V
                  + n_rep * s_len * 4                     # f32 scores
                  + 2 * n_rep * d * 4)                    # q + out
    return vmem_bytes <= _VMEM_BUDGET_BYTES


def decode_attention(q, cache_k, cache_v, pos):
    """One-token attention against the cache, GQA-native.

    q [B, 1, H, D]; cache_k/v [B, Hkv, S, D] (kernel layout — heads
    major, like the flash kernels, so the pallas block's trailing dims
    are the contiguous [S, D] slice); slots <= pos valid.
    Returns [B, 1, H, D] in q's dtype.
    """
    b, _, hq, d = q.shape
    hkv, s_len = cache_k.shape[1], cache_k.shape[2]
    n_rep = hq // hkv

    # Shapes are static, so the VMEM gate is a trace-time choice.
    if not kernel_fits_vmem(q.shape, cache_k.shape, cache_k.dtype):
        return _decode_attention_xla(q, cache_k, cache_v, pos)

    if not use_pallas("decode_attention", (q, cache_k, cache_v),
                      _INTERPRET):
        return _decode_attention_xla(q, cache_k, cache_v, pos)

    qg = q.reshape(b, hkv, n_rep, d)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    kernel = functools.partial(_kernel, scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv),
            in_specs=[
                pl.BlockSpec((None, None, n_rep, d),
                             lambda bi, gi, *a: (bi, gi, 0, 0)),
                pl.BlockSpec((None, None, s_len, d),
                             lambda bi, gi, *a: (bi, gi, 0, 0)),
                pl.BlockSpec((None, None, s_len, d),
                             lambda bi, gi, *a: (bi, gi, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, n_rep, d),
                                   lambda bi, gi, *a: (bi, gi, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d), q.dtype),
        interpret=_INTERPRET,
        metadata={"kernel": "hvd_decode_attention"},
    )(pos_arr, qg, cache_k, cache_v)
    return out.reshape(b, 1, hq, d)


def decode_attention_ragged(q, cache_k, cache_v, lengths, k_new, v_new,
                            k_scale=None, v_scale=None):
    """One-token attention for a CONTINUOUS-BATCHING step: every row of
    the batch sits at its OWN position (``lengths[b]`` — the count of
    valid cached slots), and the new token's k/v ride alongside instead
    of being written into the cache first (the serving engine owns the
    paged write; see horovod_tpu/serving/kvcache.py).

    q [B, 1, H, D]; cache_k/v [B, Hkv, S, D] gathered from the block
    pool (slots < lengths[b] valid); k_new/v_new [B, Hkv, 1, D] — this
    step's projections, attended as position lengths[b]. Masked cache
    slots softmax to exactly 0.0 (exp underflow at -1e30), so the
    result equals attention over the first lengths[b]+1 positions.

    int8 paged read path (``k_scale``/``v_scale`` [B, Hkv, S]): the
    cache arrives int8 with per-block scales expanded per slot, and the
    dequant happens HERE — widen to f32, scale, and accumulate in f32
    (``preferred_element_type``), the quantize-narrow/accumulate-wide
    recipe of the bf16 wire codec and EQuARX (arXiv:2506.17615).
    Numeric recipe otherwise matches :func:`decode_attention`: f32
    scores/softmax, probabilities cast to the value dtype before a
    f32-accumulated PV.
    """
    b, _, hq, d = q.shape
    hkv, s_len = cache_k.shape[1], cache_k.shape[2]
    n_rep = hq // hkv
    qg = q.reshape(b, hkv, n_rep, d)
    if k_scale is not None:
        kc = cache_k.astype(jnp.float32) * k_scale[..., None]
        vc = cache_v.astype(jnp.float32) * v_scale[..., None]
    else:
        kc, vc = cache_k, cache_v
    s = jnp.einsum("bgrd,bgsd->bgrs", qg, kc,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    valid = (jnp.arange(s_len)[None, :]
             < jnp.asarray(lengths, jnp.int32)[:, None])
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    s_self = jnp.einsum("bgrd,bgsd->bgrs", qg, k_new.astype(kc.dtype),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    s = jnp.concatenate([s, s_self], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    # bf16-probabilities recipe: cast to the (dequantized) value dtype.
    p = p.astype(vc.dtype)
    out = (jnp.einsum("bgrs,bgsd->bgrd", p[..., :s_len], vc,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bgrs,bgsd->bgrd", p[..., s_len:],
                        v_new.astype(vc.dtype),
                        preferred_element_type=jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def _decode_attention_xla(q, cache_k, cache_v, pos):
    """Reference-math einsum chain (off-TPU operands, and shapes past
    the kernel's VMEM gate; same numerics).
    cache_k/v in the [B, Hkv, S, D] kernel layout."""
    b, _, hq, d = q.shape
    hkv, s_len = cache_k.shape[1], cache_k.shape[2]
    n_rep = hq // hkv
    qg = q.reshape(b, hkv, n_rep, d)
    s = jnp.einsum("bgrd,bgsd->bgrs", qg, cache_k,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    valid = jnp.arange(s_len) <= pos
    s = jnp.where(valid[None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", p.astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)
