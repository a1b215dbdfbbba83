"""Flash attention as a pallas TPU kernel (forward + custom-VJP backward).

Why: plain attention materializes the [B,H,T,T] score matrix; at the
bench shape (B8 H16 T2048 f32) that is 2 GB per layer — XLA must either
spill to HBM or the model must full-remat (33% extra FLOPs). Blockwise
online-softmax attention keeps everything in VMEM; the residuals are
just the output and the per-row logsumexp.

Kernel design (v5e-friendly):
- layout [B, H, T, D]; 4-D grid over (batch, head, outer-block,
  inner-block) with the INNER loop as the last grid dimension, so
  every operand is streamed block-by-block: VMEM residency is
  O(block_q·block_k + (block_q+block_k)·D) — independent of sequence
  length. (The round-3 kernels kept whole-(b,h) K/V or Q/dO slices
  resident, which capped the single-chip backward at T≈4096 with a
  scoped-VMEM compile error.)
- online-softmax / gradient accumulators are f32 VMEM scratch that
  persists across the inner grid steps; outputs are written on the
  last inner step. bf16 matmul inputs (MXU native),
  `preferred_element_type=f32`.
- causal masking by global position iota; whole causally-irrelevant
  blocks are skipped with `pl.when` (the block's DMA still streams,
  but it costs bandwidth only — no MXU work).
- backward = two kernels (dkv over kv-blocks with q streamed, dq over
  q-blocks with kv streamed), the standard flash decomposition with
  the saved logsumexp.

Operands that live off-TPU take the XLA blockwise implementation
(pallas interpret mode is too slow for real runs; CPU tests exercise the
same math via ``horovod_tpu.parallel.blockwise_attention``). The choice
is ``ops._platform.use_pallas``: made from the operands' device, so a
TPU run can never land on the reference path.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas

_NEG = -1e30

# Tests set this to run the kernels in pallas interpret mode on CPU —
# the only way the TPU code paths (incl. the bias branches) get CI
# coverage without a chip.
_INTERPRET = False


def _fwd_kernel(*refs, scale, causal, has_bias, has_offsets):
    # refs = ([offs_ref,] q_ref, k_ref, v_ref, [bias_ref,] o_ref,
    # lse_ref, acc_ref, m_ref, l_ref). grid = (b, h, iq, jj): q/o/lse
    # blocks are keyed by iq (constant across the inner jj steps), k/v
    # stream per jj; the online-softmax state lives in f32 VMEM scratch
    # persisted across jj and the output is written on the last step.
    # bias is a per-key additive f32 row [1, Tk] (padding masks).
    # offs_ref is an SMEM int32 [2] = (q_offset, kv_offset): GLOBAL
    # positions for causal masking when the call sees only a chunk of
    # the sequence (ring attention steps) — dynamic, so one compiled
    # kernel serves every ring step.
    if has_offsets:
        offs_ref, q_ref, k_ref, v_ref, *rest = refs
    else:
        (q_ref, k_ref, v_ref), rest = refs[:3], list(refs[3:])
        offs_ref = None
    if has_bias:
        bias_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        bias_ref = None
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    iq = pl.program_id(2)
    jj = pl.program_id(3)
    n_jj = pl.num_programs(3)

    @pl.when(jj == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros((bq, d), jnp.float32)
        m_ref[:, :] = jnp.full((bq, 1), _NEG, jnp.float32)
        l_ref[:, :] = jnp.zeros((bq, 1), jnp.float32)

    q_base = offs_ref[0] if has_offsets else 0
    kv_base = offs_ref[1] if has_offsets else 0
    # Whole-block causal skip: the block's first GLOBAL kv position must
    # not be past this q block's last GLOBAL row (with offsets the bases
    # are scalar-prefetched SMEM values, so the predicate is dynamic —
    # a causal ring's fully-future chunks cost zero matmuls).
    relevant = True
    if causal:
        relevant = kv_base + jj * bk <= q_base + (iq + 1) * bq - 1

    @pl.when(relevant)
    def _update():
        q = q_ref[:, :]
        k_blk = k_ref[:, :]
        v_blk = v_ref[:, :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_base + iq * bq + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kv_pos = kv_base + jj * bk + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= kv_pos, s, _NEG)
        if has_bias:
            s = s + bias_ref[:, :]
        m = m_ref[:, :]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[:, :] = l_ref[:, :] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[:, :] = acc_ref[:, :] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :] = m_new

    @pl.when(jj == n_jj - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :], 1e-30)
        # A q row with ZERO valid keys (possible in ring/offset chunks
        # whose kv chunk is entirely future) keeps m == _NEG, so
        # p = exp(s - m) = 1 uniformly and acc/l would be mean-of-V.
        # Zero those rows: their lse stays ~_NEG, so ring logsumexp
        # merging weights them out anyway, but the standalone chunk
        # output must be correct in its own right.
        valid = m_ref[:, :] > _NEG / 2
        o_ref[:, :] = jnp.where(
            valid, acc_ref[:, :] / l, 0.0).astype(o_ref.dtype)
        lse_ref[:, :] = m_ref[:, :] + jnp.log(l)


def _bwd_dkv_kernel(*refs, scale, causal, has_bias, has_offsets):
    # grid = (b, h, jk, iq): k/v/dk/dv blocks are keyed by jk (constant
    # across the inner iq steps), q/do/lse/delta stream per iq; dk/dv
    # accumulate in f32 VMEM scratch and are written on the last step.
    if has_offsets:
        offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, \
            *rest = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
        offs_ref = None
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        bias_ref = None
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    jk = pl.program_id(2)
    iq = pl.program_id(3)
    n_iq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:, :] = jnp.zeros((bk, d), jnp.float32)
        dv_acc[:, :] = jnp.zeros((bk, d), jnp.float32)

    q_base = offs_ref[0] if has_offsets else 0
    kv_base = offs_ref[1] if has_offsets else 0
    relevant = True
    if causal:
        # This q block contributes iff its last GLOBAL row reaches the
        # kv block's first GLOBAL position.
        relevant = q_base + (iq + 1) * bq - 1 >= kv_base + jk * bk

    @pl.when(relevant)
    def _update():
        k = k_ref[:, :]
        v = v_ref[:, :]
        qi = q_ref[:, :]
        doi = do_ref[:, :]
        lse = lse_ref[:, :]
        delta = delta_ref[:, :]
        s = jax.lax.dot_general(
            qi, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_base + iq * bq + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kv_pos = kv_base + jk * bk + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= kv_pos, s, _NEG)
        if has_bias:
            s = s + bias_ref[:, :]
        # For a q row with ZERO valid keys lse is itself ~_NEG, so
        # exp(s - lse) rounds to 1 per masked key — guard on s directly
        # (valid rows are unaffected: their masked keys underflow to 0).
        p = jnp.where(s > _NEG / 2, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_acc[:, :] = dv_acc[:, :] + jax.lax.dot_general(
            p.astype(doi.dtype), doi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            doi, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:, :] = dk_acc[:, :] + jax.lax.dot_general(
            ds.astype(qi.dtype), qi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == n_iq - 1)
    def _finish():
        dk_ref[:, :] = dk_acc[:, :].astype(dk_ref.dtype)
        dv_ref[:, :] = dv_acc[:, :].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, has_bias, has_offsets):
    # grid = (b, h, iq, jj): q/do/lse/delta/dq blocks are keyed by iq
    # (constant across the inner jj steps), k/v stream per jj; dq
    # accumulates in f32 VMEM scratch, written on the last step.
    if has_offsets:
        offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, \
            *rest = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
        offs_ref = None
    if has_bias:
        bias_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        bias_ref = None
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    iq = pl.program_id(2)
    jj = pl.program_id(3)
    n_jj = pl.num_programs(3)

    @pl.when(jj == 0)
    def _init():
        dq_acc[:, :] = jnp.zeros((bq, d), jnp.float32)

    q_base = offs_ref[0] if has_offsets else 0
    kv_base = offs_ref[1] if has_offsets else 0
    relevant = True
    if causal:
        relevant = kv_base + jj * bk <= q_base + (iq + 1) * bq - 1

    @pl.when(relevant)
    def _update():
        q = q_ref[:, :]
        do = do_ref[:, :]
        lse = lse_ref[:, :]
        delta = delta_ref[:, :]
        k_blk = k_ref[:, :]
        v_blk = v_ref[:, :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_base + iq * bq + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kv_pos = kv_base + jj * bk + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= kv_pos, s, _NEG)
        if has_bias:
            s = s + bias_ref[:, :]
        # Same zero-valid-key guard as the dkv kernel (see there).
        p = jnp.where(s > _NEG / 2, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:, :] = dq_acc[:, :] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jj == n_jj - 1)
    def _finish():
        dq_ref[:, :] = dq_acc[:, :].astype(dq_ref.dtype)


def _pallas_dispatch(name, kernel, grid, in_specs, out_specs, out_shape,
                     args, offsets, scratch_shapes):
    """Shared fwd/bwd dispatch: plain grid, or scalar-prefetch grid
    spec when dynamic offsets ride along (the SMEM scalars arrive
    before the kernel body and every index map). ``scratch_shapes``
    are the f32 VMEM accumulators that persist across the inner grid
    dimension. ``name`` (``hvd_flash_fwd``, ``hvd_flash_bwd_dq``,
    ``hvd_flash_bwd_dkv``) tells the three kernels apart in a device
    trace: as ``metadata`` it rides in the custom call's
    ``frontend_attributes={kernel_metadata={"kernel":...}}``, which an
    op's event on the v5e shows (read off a chip trace, PR 25). The
    pallas ``name=`` would not: it names the instruction only while
    jax keeps full tracebacks in locations, and
    ``enable_compile_cache()`` turns those off."""
    metadata = {"kernel": name}
    if offsets is not None:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            out_shape=out_shape, interpret=_INTERPRET, metadata=metadata,
        )(offsets, *args)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=_INTERPRET, metadata=metadata,
        scratch_shapes=scratch_shapes)(*args)


def _pick_block(t, want):
    """Largest divisor of t that is <= want (t is a power-of-two seq in
    practice; degrade gracefully otherwise)."""
    b = min(want, t)
    while t % b:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    o, _ = _flash_fwd_impl(q, k, v, None, causal, block_q, block_k)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_biased(q, k, v, bias, causal, block_q, block_k):
    o, _ = _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k)
    return o


def _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k,
                    offsets=None):
    b, h, t, d = q.shape
    tk = k.shape[2]
    # GQA-native: k/v arrive UNREPEATED ([B, Hkv, T, D]); each query
    # head's block specs index kv-head hi // n_rep, so the n_rep-fold
    # expansion never materializes in HBM (the repeat would cost a copy
    # per call and double the saved k/v residuals).
    n_rep = h // k.shape[1]
    scale = d ** -0.5
    grid = (b, h, t // block_q, tk // block_k)
    has_bias = bias is not None
    has_offsets = offsets is not None
    kernel = functools.partial(_fwd_kernel, scale=scale,
                               causal=causal, has_bias=has_bias,
                               has_offsets=has_offsets)
    # With scalar prefetch the index maps receive the scalar ref as a
    # trailing arg; *a soaks it up either way.
    in_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi // n_rep, ji, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi // n_rep, ji, 0)),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, hi, qi, ji, *a: (bi, 0, ji)))
        args.append(bias)
    out_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_q, 1),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),   # acc
        pltpu.VMEM((block_q, 1), jnp.float32),   # m
        pltpu.VMEM((block_q, 1), jnp.float32),   # l
    ]
    return _pallas_dispatch("hvd_flash_fwd", kernel, grid, in_specs,
                            out_specs, out_shape, args, offsets, scratch)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, None, causal, block_q, block_k)
    # Residuals named for remat policies: an outer checkpoint_name on
    # the returned o covers only the PRIMAL output — the residual o/lse
    # here are distinct jaxpr vars, and leaving them unnamed makes
    # jax.checkpoint re-run this whole kernel in the backward pass just
    # to regenerate lse (a [B,H,T,1] f32 — ~1 MB/layer at bench shapes,
    # vs a full flash forward to recompute). Profiled round 3: the
    # rerun cost ~12% of the train step.
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_biased_fwd(q, k, v, bias, causal, block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, bias, o, lse)


def _flash_bwd_impl(q, k, v, bias, o, lse, do, causal, block_q, block_k,
                    offsets=None, dlse=None):
    b, h, t, d = q.shape
    hkv = k.shape[1]
    tk = k.shape[2]
    n_rep = h // hkv
    scale = d ** -0.5
    has_bias = bias is not None
    has_offsets = offsets is not None
    delta = (do.astype(jnp.float32)
             * o.astype(jnp.float32)).sum(-1, keepdims=True)
    if dlse is not None:
        # An incoming lse cotangent folds into delta: ds = p*(dp - delta)
        # becomes p*(dp - delta + dlse), i.e. delta -= dlse.
        delta = delta - dlse.astype(jnp.float32)

    def call(name, kernel, grid, in_specs, out_specs, out_shape, args,
             scratch):
        return _pallas_dispatch(name, kernel, grid, in_specs, out_specs,
                                out_shape, args, offsets, scratch)

    # dkv: grid (b, h, jk, iq) — q/do/lse/delta stream over the inner
    # iq dimension, k/v and the dk/dv accumulators stay pinned per jk.
    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, has_bias=has_bias,
                                   has_offsets=has_offsets)
    in_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, jk, iq, *a: (bi, hi, iq, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, jk, iq, *a: (bi, hi // n_rep, jk, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, jk, iq, *a: (bi, hi // n_rep, jk, 0)),
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, jk, iq, *a: (bi, hi, iq, 0)),
        pl.BlockSpec((None, None, block_q, 1),
                     lambda bi, hi, jk, iq, *a: (bi, hi, iq, 0)),
        pl.BlockSpec((None, None, block_q, 1),
                     lambda bi, hi, jk, iq, *a: (bi, hi, iq, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, hi, jk, iq, *a: (bi, 0, jk)))
        args.append(bias)
    # dk/dv come out PER QUERY HEAD ([B, H, Tk, D]); the sum over each
    # kv-head's n_rep sharing query heads happens outside the kernel
    # (one cheap XLA reduction — keeps the kernel free of cross-kv-head
    # accumulation state).
    dk, dv = call(
        "hvd_flash_bwd_dkv", dkv_kernel,
        (b, h, tk // block_k, t // block_q), in_specs,
        [
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, jk, iq, *a: (bi, hi, jk, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, jk, iq, *a: (bi, hi, jk, 0)),
        ],
        [
            jax.ShapeDtypeStruct((b, h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, d), v.dtype),
        ],
        args,
        [pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32)])
    if n_rep > 1:
        dk = dk.astype(jnp.float32).reshape(b, hkv, n_rep, tk, d) \
            .sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(b, hkv, n_rep, tk, d) \
            .sum(axis=2).astype(v.dtype)

    # dq: grid (b, h, iq, jj) — k/v stream over the inner jj dimension,
    # q/do/lse/delta and the dq accumulator stay pinned per iq.
    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  causal=causal, has_bias=has_bias,
                                  has_offsets=has_offsets)
    in_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi // n_rep, ji, 0)),
        pl.BlockSpec((None, None, block_k, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi // n_rep, ji, 0)),
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_q, 1),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_q, 1),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, hi, qi, ji, *a: (bi, 0, ji)))
        args.append(bias)
    dq = call(
        "hvd_flash_bwd_dq", dq_kernel,
        (b, h, t // block_q, tk // block_k), in_specs,
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        jax.ShapeDtypeStruct(q.shape, q.dtype), args,
        [pltpu.VMEM((block_q, d), jnp.float32)])
    return dq, dk, dv


def _flash_bwd(causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, None, o, lse, do, causal, block_q,
                           block_k)


def _flash_biased_bwd(causal, block_q, block_k, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, bias, o, lse, do, causal,
                                 block_q, block_k)
    # The bias is a padding mask (piecewise-constant); its cotangent is
    # never consumed, so report zeros rather than paying a reduction.
    return dq, dk, dv, jnp.zeros_like(bias)


_flash.defvjp(_flash_fwd, _flash_bwd)
_flash_biased.defvjp(_flash_biased_fwd, _flash_biased_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_offsets(q, k, v, offsets, causal, block_q, block_k):
    """Flash attention over a K/V CHUNK with dynamic global-position
    offsets (SMEM scalars — one compiled kernel serves every ring
    step). Returns (o, lse): the normalized chunk output plus its
    logsumexp, exactly what ring attention's online-softmax merge
    needs. q [B,H,Tq,D]; k,v [B,Hkv,Tk,D]; offsets int32 [2] =
    (global q start, global kv start)."""
    return _flash_fwd_impl(q, k, v, None, causal, block_q, block_k,
                           offsets=offsets)


def _flash_offsets_fwd(q, k, v, offsets, causal, block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, None, causal, block_q, block_k,
                             offsets=offsets)
    # Same residual naming as _flash_fwd: without it, remat="attn"
    # re-runs every ring step's forward kernel in backward just to
    # regenerate these (n ring steps per layer).
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, offsets, o, lse)


def _flash_offsets_bwd(causal, block_q, block_k, res, cts):
    q, k, v, offsets, o, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd_impl(q, k, v, None, o, lse, do, causal,
                                 block_q, block_k, offsets=offsets,
                                 dlse=dlse)
    import numpy as _np

    d_offs = _np.zeros(offsets.shape, jax.dtypes.float0)
    return dq, dk, dv, d_offs


_flash_offsets.defvjp(_flash_offsets_fwd, _flash_offsets_bwd)


def flash_attention_chunk(q, k, v, q_offset, kv_offset, causal=True,
                          block_q=1024, block_k=1024):
    """One ring-attention step on the pallas kernels: attention of the
    local queries against ONE K/V chunk, with global positions for the
    causal mask. Layout [B, H(q)/Hkv(kv), T, D] (kernel layout — ring
    loops keep tensors there to avoid per-step transposes). Returns
    ``(o, lse)`` ready for logsumexp merging; differentiable (the lse
    cotangent folds into the backward's delta).
    """
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(k.shape[2], block_k)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    return _flash_offsets(q, k, v, offsets, causal, bq, bk)


def _masked_attention_xla(q, k, v, kv_bias, causal):
    """Reference math with a per-key additive bias (CPU tests;
    shapes there are tiny, so materializing scores is fine)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / (d ** 0.5)
    s = s + kv_bias[:, None, None, :].astype(jnp.float32)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _kernel_mesh_specs(mesh, q, k):
    """PartitionSpecs that split the kernel call over ``mesh``: batch
    over the data-parallel axes, heads over ``tensor`` when it divides
    both head counts (GQA groups stay aligned), everything else whole.
    Returns ``(qkv_spec_for(heads), bias_spec)``."""
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch)
    if q.shape[0] % n_batch:
        raise ValueError(
            f"flash_attention: batch {q.shape[0]} does not divide over "
            f"mesh axes {batch} (size {n_batch}); the pallas kernel "
            "cannot pad a shard the way GSPMD would")
    tp = mesh.shape.get("tensor", 1)
    heads = "tensor" if tp > 1 and q.shape[2] % tp == 0 \
        and k.shape[2] % tp == 0 else None
    return P(batch or None, None, heads, None), P(batch or None, None)


def flash_attention(q, k, v, causal=True, kv_bias=None, block_q=1024,
                    block_k=1024, mesh=None):
    """Flash attention. q,k,v: [B, T, H, D] (framework layout; kv heads
    may be fewer — GQA is handled natively: the kernels index kv-head
    ``query_head // n_rep``, so the expansion never materializes in
    HBM). Returns [B, T, H, D].

    ``kv_bias`` is an optional [B, Tk] f32 additive per-key bias —
    padding masks pass 0 for real keys and a large negative for padding
    (BERT-style bidirectional attention over ragged batches). It is
    treated as a CONSTANT (stop_gradient on every path): masks have no
    useful gradient, and the TPU kernel does not compute one.

    Operands on a TPU: pallas kernel. Elsewhere: the XLA blockwise
    implementation (same math, used by CPU tests).

    ``mesh``: the mesh the surrounding jit program is partitioned over,
    if any. GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so on a multi-device mesh the kernel runs under
    ``jax.shard_map`` — batch over ``data``/``fsdp``, heads over
    ``tensor`` — and each device launches it on its own shard. The
    reference math needs no such help and ignores ``mesh``.
    """
    from horovod_tpu.parallel.ring_attention import _repeat_kv

    if kv_bias is not None:
        kv_bias = lax.stop_gradient(kv_bias)
    n_rep = q.shape[2] // k.shape[2]
    # _INTERPRET forces the pallas path off-TPU so tests cover the real
    # kernel code (interpret mode) instead of the reference math.
    if not use_pallas("flash_attention", (q, k, v), _INTERPRET):
        # The reference paths name their output for remat="attn" here —
        # keeping the naming NEXT TO the platform predicate means a
        # future reference branch can't silently lose the saved
        # activation (the pallas path instead names its VJP residuals,
        # flash_o/flash_lse, in _flash_fwd).
        if kv_bias is not None:
            return checkpoint_name(
                _masked_attention_xla(q, _repeat_kv(k, n_rep),
                                      _repeat_kv(v, n_rep), kv_bias,
                                      causal), "attn_out")
        from horovod_tpu.parallel.ring_attention import blockwise_attention

        return checkpoint_name(blockwise_attention(q, k, v, causal=causal),
                               "attn_out")

    def kernel(q, k, v, kv_bias=None):
        # [B,T,H,D] -> [B,H,T,D]; k/v stay at Hkv heads — the kernels
        # index kv-head = query-head // n_rep, so GQA expansion never
        # hits HBM.
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        t = qt.shape[2]
        bq = _pick_block(t, block_q)
        bk = _pick_block(t, block_k)
        if kv_bias is not None:
            bias = kv_bias.astype(jnp.float32)[:, None, :]  # [B, 1, Tk]
            o = _flash_biased(qt, kt, vt, bias, causal, bq, bk)
        else:
            o = _flash(qt, kt, vt, causal, bq, bk)
        return o.transpose(0, 2, 1, 3)

    operands = (q, k, v) if kv_bias is None else (q, k, v, kv_bias)
    if mesh is None or mesh.size == 1:
        return kernel(*operands)
    qkv_spec, bias_spec = _kernel_mesh_specs(mesh, q, k)
    in_specs = (qkv_spec,) * 3 + ((bias_spec,) if kv_bias is not None
                                  else ())
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=qkv_spec, check_vma=False)(*operands)
