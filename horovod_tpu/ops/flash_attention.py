"""Flash attention as a pallas TPU kernel (forward + custom-VJP backward).

Why: plain attention materializes the [B,H,T,T] score matrix; at the
bench shape (B8 H16 T2048 f32) that is 2 GB per layer — XLA must either
spill to HBM or the model must full-remat (33% extra FLOPs). Blockwise
online-softmax attention keeps everything in VMEM; the residuals are
just the output and the per-row logsumexp.

Kernel design (v5e-friendly):
- layout [B, H, T, D]; 4-D grid over (batch, head, outer-block,
  inner-block) with the INNER loop as the last grid dimension, so
  q, k, v, do and the outputs are streamed block-by-block; the forward
  holds O(block_q·block_k + (block_q+block_k)·D) of VMEM whatever the
  sequence length. (The round-3 kernels kept whole-(b,h) K/V or Q/dO
  slices resident, which capped the single-chip backward at T≈4096 with
  a scoped-VMEM compile error.)
- online-softmax / gradient accumulators are f32 VMEM scratch that
  persists across the inner grid steps; outputs are written on the
  last inner step. bf16 matmul inputs (MXU native),
  `preferred_element_type=f32`.
- three kinds of score tile, forward and backward alike, told apart
  from the blocks' GLOBAL positions (`_tile_kinds`; `tile_counts` says
  how many of each a call has): SKIPPED, wholly above the diagonal
  (`pl.when`: no MXU or VPU work, and no DMA either: its index maps
  name the block of the nearest tile that runs, which is already
  there); INTERIOR, wholly below it, computed with no iota, compare or
  select; STRADDLING, masked by global position.
  Causal at 1024 x 1024 blocks a (batch, head) at T = 4096 has
  6 / 6 / 4 of them, at T = 8192 28 / 28 / 8; a non-causal call has
  interior tiles only. A call with a ``window`` sees a band under the
  diagonal: tiles wholly below it are skipped too, and both edges mask
  (T = 8192, window 2048: 43 / 7 / 14).
- the softmax scale is applied to the [block_q, D] q tile, and to the
  finished dq / dk accumulators, never to a [block_q, block_k] plane;
  the zero-valid-key guard of the backward runs only where such a row
  can exist (a bias; a straddling tile of a call with offsets).
- backward = ONE kernel (`hvd_flash_bwd_fused`), grid (b, h, jk, iq):
  per tile s, p = exp(s - lse), dp and ds are formed once and feed all
  three products (5 matmuls; the dkv + dq pair it replaced did 7 and
  the elementwise chain twice). The tile is held TRANSPOSED, keys on
  the rows (s_t = k q^T): dv += p_t do and dk += ds_t q are plain
  products, dq += ds_t^T k the one with a transposed LHS, and the row
  statistics broadcast down the sublanes as they arrive.
  dk / dv accumulate per jk as in the forward's mirror image;
  dq accumulates in an f32 VMEM scratch of the whole (b, h) slice,
  [T, D] (2 MiB at T = 4096, 8 MiB at 16384), and each block of it is
  cast and written while the last jk passes: no f32 dq in HBM, no
  second pass. The kernel asks for its own VMEM (`_bwd_vmem_bytes`).
- the row statistics (the forward's `lse` result, the backward's `lse`
  and `delta` operands) cross the call boundary as f32 [B, H, 1, T],
  T on the lanes, in blocks [1, block_q]: 2 MB a call at B2 H32 T8192.
  As [B, H, T, 1] each filled a whole 128-lane tile a value (268 MB),
  and a layer under remat held that from its forward to its backward.
  The forward turns its [block_q, 1] column into the row once a q
  block (`_column_to_row`); the backward uses rows as they are.

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


def _tile_kinds(causal, q_first, block_q, kv_first, block_k, window=0):
    """``(interior, straddling)`` of the score tile whose first GLOBAL
    query row is ``q_first`` and whose first GLOBAL key is ``kv_first``.
    Interior: the tile's last key is visible to its first row, so no
    element is masked. Straddling: the diagonal crosses it. Neither: no
    element is valid and the tile is skipped. With a ``window`` W (key j
    visible to row i where ``i - W < j <= i``) the visible keys are a
    BAND: a tile is interior only if its first key is also inside the
    window of its last row, is skipped also where its last key lies at
    or before ``q_first - W`` (wholly below the band), and straddles
    where either edge crosses it. One formula for Python ints
    (``tile_counts``) and for traced scalars (the kernels, where the
    bases are program ids plus, with offsets, SMEM values); whether
    there is a window is decided in Python, so a call without one
    traces what it always did."""
    if not causal:
        return True, False
    q_last = q_first + block_q - 1
    kv_last = kv_first + block_k - 1
    interior = kv_last <= q_first
    straddling = (kv_first <= q_last) & (kv_last > q_first)
    if window:
        # The band's lower edge: wholly inside it, or wholly below it.
        inside = kv_first > q_last - window
        straddling = (kv_first <= q_last) & (kv_last > q_first - window) \
            & ((kv_last > q_first) | (kv_first <= q_last - window))
        interior = interior & inside
    return interior, straddling


def tile_counts(t, tk, block_q, block_k, causal, q_offset=0, kv_offset=0,
                window=0):
    """``(skipped, interior, straddling)`` score tiles of one (batch,
    head) slice, by the predicate the kernels run on: what the forward
    and the backward skip, run without a mask, and run with one. Causal
    at 1024 x 1024 blocks: T = 4096 gives 6 / 6 / 4, T = 8192 gives
    28 / 28 / 8, and with a window of 2048 at T = 8192 43 / 7 / 14 (21
    tiles run of the 36 a full layer runs); a non-causal call has
    interior tiles only."""
    interior = straddling = 0
    for iq in range(t // block_q):
        for jk in range(tk // block_k):
            inside, crossing = _tile_kinds(
                causal, q_offset + iq * block_q, block_q,
                kv_offset + jk * block_k, block_k, window)
            interior += bool(inside)
            straddling += bool(crossing)
    n = (t // block_q) * (tk // block_k)
    return n - interior - straddling, interior, straddling


def _for_each_kind(causal, q_first, block_q, kv_first, block_k, update,
                   window=0):
    """Run ``update(masked)`` as one score tile needs it: not at all
    (skipped), unmasked (interior), or masked (straddling)."""
    interior, straddling = _tile_kinds(causal, q_first, block_q,
                                       kv_first, block_k, window)
    pl.when(interior)(functools.partial(update, False))
    pl.when(straddling)(functools.partial(update, True))


def _positions(first, n, axis):
    """GLOBAL positions ``first .. first + n`` along ``axis`` of a score
    tile, one wide along the other: a column or a row, never a plane."""
    shape = (n, 1) if axis == 0 else (1, n)
    return first + lax.broadcasted_iota(jnp.int32, shape, axis)


def _scores(qs, k, q_first, kv_first, masked, bias, window=0,
            keys_on_rows=False):
    """Scores of one tile from the SCALED q tile, in f32: ``qs k^T``
    [block_q, block_k] (the forward), or with ``keys_on_rows`` its
    transpose ``k qs^T`` [block_k, block_q] (the backward, whose row
    statistics then broadcast down the sublanes as they arrive). The
    causal mask (and, with a ``window``, the band's lower edge) only
    where an edge crosses the tile (``masked``; ``q_first`` /
    ``kv_first`` are the GLOBAL positions of its first query and first
    key), the per-key bias where the call has one (a row, or with
    ``keys_on_rows`` a column)."""
    a, b = (k, qs) if keys_on_rows else (qs, k)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if masked:
        # Query positions along one axis against key positions along the
        # other: no [block_q, block_k] iota plane is ever built.
        q_pos = _positions(q_first, qs.shape[0], 1 if keys_on_rows else 0)
        kv_pos = _positions(kv_first, k.shape[0], 0 if keys_on_rows else 1)
        visible = q_pos >= kv_pos
        if window:
            visible = visible & (kv_pos > q_pos - window)
        s = jnp.where(visible, s, _NEG)
    if bias is not None:
        s = s + bias
    return s


def _column_to_row(col):
    """[n, 1] -> [1, n]: a lane-broadcast, one 32-bit 2-D transpose, row
    0 (128 vregs through the XLU at n = 1024). How a statistic the
    kernel holds a row of q apiece leaves for HBM with T on the lanes."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1, :]


def _row_to_column(row):
    """[1, n] -> [n, 1], the other way: the per-key bias row, which the
    backward's transposed tile wants down its rows."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _scaled(q, scale):
    # The softmax scale goes onto the [block_q, D] q tile, not onto the
    # [block_q, block_k] score plane (one rounding to the operand dtype,
    # the same tile in the forward and the backward).
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _fwd_kernel(*refs, scale, causal, has_bias, has_offsets, window=0):
    # refs = ([offs_ref,] q_ref, k_ref, v_ref, [bias_ref,] o_ref,
    # lse_ref, qs_ref, acc_ref, m_ref, l_ref). grid = (b, h, iq, jj):
    # q/o/lse blocks are keyed by iq (constant across the inner jj
    # steps), k/v stream per jj; the scaled q tile and the
    # online-softmax state live in VMEM scratch persisted across jj and
    # the output is written on the last step. The state is a column
    # ([block_q, 1], a value a row of scores); the lse block is a ROW,
    # [1, block_q], so it lies in HBM with T on the lanes.
    # bias is a per-key additive f32 row [1, Tk] (padding masks).
    # offs_ref is an SMEM int32 [2] = (q_offset, kv_offset): GLOBAL
    # positions for causal masking when the call sees only a chunk of
    # the sequence (ring attention steps) — dynamic, so one compiled
    # kernel serves every ring step.
    if has_offsets:
        offs_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    bias_ref = rest.pop(0) if has_bias else None
    o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref = rest
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    jj = pl.program_id(3)
    n_jj = pl.num_programs(3)

    @pl.when(jj == 0)
    def _init():
        qs_ref[:, :] = _scaled(q_ref[:, :], scale)
        acc_ref[:, :] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[:, :] = jnp.full((bq, 1), _NEG, jnp.float32)
        l_ref[:, :] = jnp.zeros((bq, 1), jnp.float32)

    # Three kinds of tile by GLOBAL position (with offsets the bases are
    # scalar-prefetched SMEM values, so the predicates are dynamic — a
    # causal ring's fully-future chunks cost zero matmuls, its
    # fully-past chunks no mask).
    q_first = (offs_ref[0] if has_offsets else 0) + pl.program_id(2) * bq
    kv_first = (offs_ref[1] if has_offsets else 0) + jj * bk

    def update(masked):
        v_blk = v_ref[:, :]
        s = _scores(qs_ref[:, :], k_ref[:, :], q_first, kv_first, masked,
                    bias_ref[:, :] if has_bias else None, window)
        m = m_ref[:, :]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[:, :] = l_ref[:, :] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[:, :] = acc_ref[:, :] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :] = m_new

    _for_each_kind(causal, q_first, bq, kv_first, bk, update, window)

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
        lse_ref[:, :] = _column_to_row(m_ref[:, :] + jnp.log(l))


def _bwd_kernel(*refs, scale, causal, has_bias, has_offsets, window=0):
    # The whole backward in one pass: per score tile s, p, dp and ds are
    # formed ONCE and feed all three products (5 matmuls).
    # grid = (b, h, jk, iq): k/v/dk/dv blocks are keyed by jk (constant
    # across the inner iq steps), q/do/lse/delta stream per iq. dk/dv
    # accumulate in f32 VMEM scratch per jk and are written on the last
    # iq; dq accumulates in an f32 VMEM scratch of the whole (b, h)
    # slice, [T // block_q, block_q, D] indexed by iq, and each of its
    # blocks is cast and written while the last jk passes over it (the
    # dq out block is keyed by iq only then, see _flash_bwd_impl).
    # The tile is held TRANSPOSED, keys on the rows: s_t = k q^T is
    # [block_k, block_q], so the lse and delta blocks, ROWS [1, block_q]
    # as they lie in HBM (T on the lanes), broadcast down the sublanes
    # where they are used; dv += p_t do and dk += ds_t q are plain
    # products and dq += ds_t^T k is the one with a transposed LHS.
    if has_offsets:
        offs_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    bias_ref = rest.pop(0) if has_bias else None
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    jk = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init_dkv():
        dk_acc[:, :] = jnp.zeros((bk, d), jnp.float32)
        dv_acc[:, :] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(jk == 0)
    def _init_dq():
        dq_acc[iq] = jnp.zeros((bq, d), jnp.float32)

    q_first = (offs_ref[0] if has_offsets else 0) + iq * bq
    kv_first = (offs_ref[1] if has_offsets else 0) + jk * bk

    def update(masked):
        q = q_ref[:, :]
        k = k_ref[:, :]
        do = do_ref[:, :]
        s_t = _scores(_scaled(q, scale), k, q_first, kv_first, masked,
                      _row_to_column(bias_ref[:, :]) if has_bias else None,
                      window, keys_on_rows=True)
        p_t = jnp.exp(s_t - lse_ref[:, :])  # [bk, bq]
        if has_bias or (masked and has_offsets):
            # A q row with ZERO valid keys (a padded batch row; a ring
            # chunk that starts inside this q block) has lse ~_NEG
            # itself, so exp(s - lse) rounds to 1 per masked key: guard
            # on s directly. A plain causal call always has the
            # diagonal key, and an interior tile no masked one.
            p_t = jnp.where(s_t > _NEG / 2, p_t, 0.0)
        dv_acc[:, :] = dv_acc[:, :] + jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v_ref[:, :], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds without the softmax scale: dq and dk take it once, as the
        # accumulators are written.
        ds_t = (p_t * (dp_t - delta_ref[:, :])).astype(q.dtype)
        dk_acc[:, :] = dk_acc[:, :] + jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[iq] = dq_acc[iq] + jax.lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_kind(causal, q_first, bq, kv_first, bk, update, window)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finish_dkv():
        dk_ref[:, :] = (dk_acc[:, :] * scale).astype(dk_ref.dtype)
        dv_ref[:, :] = dv_acc[:, :].astype(dv_ref.dtype)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finish_dq():
        dq_ref[:, :] = (dq_acc[iq] * scale).astype(dq_ref.dtype)


def _pallas_dispatch(name, kernel, grid, in_specs, out_specs, out_shape,
                     args, offsets, scratch_shapes, vmem_limit_bytes=None,
                     window=0):
    """Shared fwd/bwd dispatch: plain grid, or scalar-prefetch grid
    spec when dynamic offsets ride along (the SMEM scalars arrive
    before the kernel body and every index map). ``scratch_shapes``
    are the VMEM buffers that persist across the inner grid
    dimensions. ``name`` (``hvd_flash_fwd``, ``hvd_flash_bwd_fused``)
    tells the kernels apart in a device trace: as ``metadata`` it rides
    in the custom call's
    ``frontend_attributes={kernel_metadata={"kernel":...}}``, which an
    op's event on the v5e shows (read off a chip trace, PR 25). The
    pallas ``name=`` would not: it names the instruction only while
    jax keeps full tracebacks in locations, and
    ``enable_compile_cache()`` turns those off. A call with a
    ``window`` carries it beside the name,
    ``kernel_metadata={"kernel":...,"window":"2048"}``: the same prefix,
    so a reader by name finds both, and one by ``"window"`` only these.
    ``vmem_limit_bytes``:
    what the kernel itself asks of VMEM, where the compiler's default
    scope (16 MiB on the v5e) is not enough."""
    common = dict(
        out_shape=out_shape, interpret=_INTERPRET,
        metadata={"kernel": name, "window": str(window)} if window
        else {"kernel": name},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes))
    if offsets is not None:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            **common)(offsets, *args)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes, **common)(*args)


def _pick_block(t, want):
    """Largest divisor of t that is <= want (t is a power-of-two seq in
    practice; degrade gracefully otherwise)."""
    b = min(want, t)
    while t % b:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, window=0, scale=None):
    o, _ = _flash_fwd_impl(q, k, v, None, causal, block_q, block_k,
                           window=window, scale=scale)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_biased(q, k, v, bias, causal, block_q, block_k):
    o, _ = _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k)
    return o


def _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k,
                    offsets=None, window=0, scale=None):
    b, h, t, d = q.shape
    tk = k.shape[2]
    # Queries and keys are ``d`` wide, values and the output ``dv``
    # (latent attention: 192 beside 128); one width where they agree.
    dv = v.shape[3]
    # GQA-native: k/v arrive UNREPEATED ([B, Hkv, T, D]); each query
    # head's block specs index kv-head hi // n_rep, so the n_rep-fold
    # expansion never materializes in HBM (the repeat would cost a copy
    # per call and double the saved k/v residuals).
    n_rep = h // k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    grid = (b, h, t // block_q, tk // block_k)
    has_bias = bias is not None
    has_offsets = offsets is not None
    kernel = functools.partial(_fwd_kernel, scale=scale,
                               causal=causal, has_bias=has_bias,
                               has_offsets=has_offsets, window=window)
    # With scalar prefetch the index maps receive the scalar ref as a
    # trailing arg; *a soaks it up either way.
    n_jj = tk // block_k

    def live(qi, ji, a):
        # A skipped tile (this kv block wholly after the q block) names
        # the block of the last tile that runs: an unchanged block index
        # starts no DMA, so the skip costs a grid step and nothing else.
        if not causal:
            return ji
        q_base, kv_base = (a[0][0], a[0][1]) if a else (0, 0)
        q_last = q_base + (qi + 1) * block_q - 1
        last = jnp.clip((q_last - kv_base) // block_k, 0, n_jj - 1)
        if not window:
            return jnp.minimum(ji, last)
        # Tiles wholly below the band come FIRST in a row of tiles:
        # they name the block of the first tile that runs.
        first = jnp.clip((q_last - block_q + 2 - window - kv_base)
                         // block_k, 0, n_jj - 1)
        return jnp.clip(ji, first, last)

    def kv_spec(width):
        return pl.BlockSpec(
            (None, None, block_k, width),
            lambda bi, hi, qi, ji, *a: (bi, hi // n_rep,
                                        live(qi, ji, a), 0))

    in_specs = [
        pl.BlockSpec((None, None, block_q, d),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        kv_spec(d), kv_spec(dv),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, hi, qi, ji, *a: (bi, 0,
                                                     live(qi, ji, a))))
        args.append(bias)
    out_specs = [
        pl.BlockSpec((None, None, block_q, dv),
                     lambda bi, hi, qi, ji, *a: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, 1, block_q),
                     lambda bi, hi, qi, ji, *a: (bi, hi, 0, qi)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
        jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_q, d), q.dtype),       # scaled q
        pltpu.VMEM((block_q, dv), jnp.float32),  # acc
        pltpu.VMEM((block_q, 1), jnp.float32),   # m
        pltpu.VMEM((block_q, 1), jnp.float32),   # l
    ]
    return _pallas_dispatch("hvd_flash_fwd", kernel, grid, in_specs,
                            out_specs, out_shape, args, offsets, scratch,
                            window=window)


def _flash_fwd(q, k, v, causal, block_q, block_k, window=0, scale=None):
    o, lse = _flash_fwd_impl(q, k, v, None, causal, block_q, block_k,
                             window=window, scale=scale)
    # Residuals named for remat policies: an outer checkpoint_name on
    # the returned o covers only the PRIMAL output — the residual o/lse
    # here are distinct jaxpr vars, and leaving them unnamed makes
    # jax.checkpoint re-run this whole kernel in the backward pass just
    # to regenerate lse (a [B,H,1,T] f32, T on the lanes: 2 MB a layer
    # at B2 H32 T8192, vs a full flash forward to recompute). Profiled
    # round 3: the rerun cost ~12% of the train step.
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_biased_fwd(q, k, v, bias, causal, block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, bias, causal, block_q, block_k)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, bias, o, lse)


def _bwd_vmem_bytes(t, block_q, block_k, d, itemsize, dv=None):
    """VMEM the one-pass backward asks for: its blocks (the pipeline
    holds each twice; a [1, block_q] f32 block of lse or delta fills
    whole 8-sublane tiles), its scratch (dq for the whole [T, D] slice,
    dk and dv for one block) and room for the f32 / operand-dtype
    planes of one score tile (s, p, dp, ds and their casts: six f32
    planes' worth). ``dv``: the width of the values where it is not
    ``d`` (q, dq, k, dk are ``d`` wide; do, v and its gradient ``dv``).
    Heads of two widths count the 128-lane tiles each width touches
    (192 takes two); heads of ONE width count ``d`` as it stands, as
    they always have: ``lfm2-8b-a1b``'s heads are 64 wide, and its
    program's text, this limit in it, is held to the byte."""
    if dv is None:
        dv = d
    else:
        d, dv = (-(-w // 128) * 128 for w in (d, dv))
    blocks = ((2 * block_q + 2 * block_k) * d
              + (block_q + 2 * block_k) * dv) * itemsize \
        + 2 * 8 * block_q * 4
    scratch = ((t + block_k) * d + block_k * dv) * 4
    return 2 * blocks + scratch + 6 * block_q * block_k * 4


def _flash_bwd_impl(q, k, v, bias, o, lse, do, causal, block_q, block_k,
                    offsets=None, dlse=None, window=0, scale=None):
    b, h, t, d = q.shape
    hkv = k.shape[1]
    tk = k.shape[2]
    dv_w = v.shape[3]
    n_rep = h // hkv
    scale = d ** -0.5 if scale is None else scale
    has_bias = bias is not None
    has_offsets = offsets is not None
    delta = (do.astype(jnp.float32)
             * o.astype(jnp.float32)).sum(-1)[:, :, None, :]
    if dlse is not None:
        # An incoming lse cotangent folds into delta: ds = p*(dp - delta)
        # becomes p*(dp - delta + dlse), i.e. delta -= dlse.
        delta = delta - dlse.astype(jnp.float32)

    n_jk, n_iq = tk // block_k, t // block_q
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               has_bias=has_bias, has_offsets=has_offsets,
                               window=window)
    # grid (b, h, jk, iq) — q/do/lse/delta stream over the inner iq
    # dimension, k/v and the dk/dv accumulators stay pinned per jk.
    # lse and delta are [B, H, 1, T], read in blocks [1, block_q].

    def live(jk, iq, a):
        # As in the forward: a skipped tile (this q block wholly before
        # the kv block) names the block of the first tile that runs.
        if not causal:
            return iq
        q_base, kv_base = (a[0][0], a[0][1]) if a else (0, 0)
        kv_first = kv_base + jk * block_k
        first = jnp.clip((kv_first - q_base) // block_q, 0, n_iq - 1)
        if not window:
            return jnp.maximum(iq, first)
        # q blocks wholly below the band come LAST in a column of
        # tiles: they name the block of the last tile that runs.
        last = jnp.clip((kv_first + block_k + window - 2 - q_base)
                        // block_q, 0, n_iq - 1)
        return jnp.clip(iq, first, last)

    def q_spec(width):
        return pl.BlockSpec(
            (None, None, block_q, width),
            lambda bi, hi, jk, iq, *a: (bi, hi, live(jk, iq, a), 0))

    def kv_spec(width):
        return pl.BlockSpec(
            (None, None, block_k, width),
            lambda bi, hi, jk, iq, *a: (bi, hi // n_rep, jk, 0))

    row_spec = pl.BlockSpec(
        (None, None, 1, block_q),
        lambda bi, hi, jk, iq, *a: (bi, hi, 0, live(jk, iq, a)))
    in_specs = [q_spec(d), kv_spec(d), kv_spec(dv_w), q_spec(dv_w),
                row_spec, row_spec]
    args = [q, k, v, do, lse, delta]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, hi, jk, iq, *a: (bi, 0, jk)))
        args.append(bias)
    # dq's out block follows iq only while the LAST jk passes (then each
    # finished block is written, cast, as the next one is entered);
    # before that it rests on block 0 and nothing is written back: no
    # f32 dq and no second pass in HBM.
    dq_spec = pl.BlockSpec(
        (None, None, block_q, d),
        lambda bi, hi, jk, iq, *a: (bi, hi,
                                    jnp.where(jk == n_jk - 1, iq, 0), 0))
    # dk/dv come out PER QUERY HEAD ([B, H, Tk, D]); the sum over each
    # kv-head's n_rep sharing query heads happens outside the kernel
    # (one cheap XLA reduction — keeps the kernel free of cross-kv-head
    # accumulation state).
    def dkv_spec(width):
        return pl.BlockSpec((None, None, block_k, width),
                            lambda bi, hi, jk, iq, *a: (bi, hi, jk, 0))

    dq, dk, dv = _pallas_dispatch(
        "hvd_flash_bwd_fused", kernel, (b, h, n_jk, n_iq), in_specs,
        [dq_spec, dkv_spec(d), dkv_spec(dv_w)],
        [
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, dv_w), v.dtype),
        ],
        args, offsets,
        [pltpu.VMEM((n_iq, block_q, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, dv_w), jnp.float32)],
        vmem_limit_bytes=_bwd_vmem_bytes(
            t, block_q, block_k, d, q.dtype.itemsize,
            None if dv_w == d else dv_w),
        window=window)
    if n_rep > 1:
        dk = dk.astype(jnp.float32).reshape(b, hkv, n_rep, tk, d) \
            .sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(b, hkv, n_rep, tk, dv_w) \
            .sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def _flash_bwd(causal, block_q, block_k, window, scale, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, None, o, lse, do, causal, block_q,
                           block_k, window=window, scale=scale)


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
    logsumexp as the kernel writes it, f32 [B,H,1,Tq], exactly what
    ring attention's online-softmax merge needs. q [B,H,Tq,D]; k,v
    [B,Hkv,Tk,D]; offsets int32 [2] = (global q start, global kv
    start)."""
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
    ``(o, lse)``, lse f32 [B, H, T], ready for logsumexp merging;
    differentiable (the lse cotangent folds into the backward's delta).
    """
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(k.shape[2], block_k)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    o, lse = _flash_offsets(q, k, v, offsets, causal, bq, bk)
    return o, lse[:, :, 0, :]


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


def _kernel_mesh_specs(mesh, batch_size, heads, kv_heads):
    """PartitionSpecs that split the kernel call over ``mesh``: batch
    over the data-parallel axes, heads over ``tensor`` when it divides
    both head counts (GQA groups stay aligned), everything else whole.
    Returns ``(batch axes, heads axis)``, either None where the mesh
    does not split it."""
    batch = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch)
    if batch_size % n_batch:
        raise ValueError(
            f"flash_attention: batch {batch_size} does not divide over "
            f"mesh axes {batch} (size {n_batch}); the pallas kernel "
            "cannot pad a shard the way GSPMD would")
    tp = mesh.shape.get("tensor", 1)
    return batch or None, "tensor" if tp > 1 and heads % tp == 0 \
        and kv_heads % tp == 0 else None


def _head_major(qt, kt, vt, kv_bias=None, *, causal, block_q, block_k,
                window, scale=None):
    """The kernels on their own layout: ``qt`` [B, H, T, D], ``kt``,
    ``vt`` [B, Hkv, T, D] (the kernels index kv-head = query-head //
    n_rep, so GQA expansion never hits HBM) -> [B, T, H, D]."""
    t = qt.shape[2]
    bq = _pick_block(t, block_q)
    bk = _pick_block(t, block_k)
    if kv_bias is not None:
        bias = kv_bias.astype(jnp.float32)[:, None, :]  # [B, 1, Tk]
        o = _flash_biased(qt, kt, vt, bias, causal, bq, bk)
    else:
        o = _flash(qt, kt, vt, causal, bq, bk, window, scale)
    return o.transpose(0, 2, 1, 3)


def _over_mesh(kernel, mesh, operands, heads_at):
    """``kernel`` on ``operands`` (q, k, v and perhaps a [B, Tk] bias),
    whose heads are dimension ``heads_at`` -> [B, T, H, D]; each device
    of a multi-device ``mesh`` on its own shard."""
    from jax.sharding import PartitionSpec as P

    if mesh is None or mesh.size == 1:
        return kernel(*operands)
    q, k = operands[:2]
    batch, heads = _kernel_mesh_specs(mesh, q.shape[0], q.shape[heads_at],
                                      k.shape[heads_at])
    spec = [batch, None, None, None]
    spec[heads_at] = heads
    in_specs = (P(*spec),) * 3 + (P(batch, None),) * (len(operands) - 3)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=P(batch, None, heads, None),
                         check_vma=False)(*operands)


def flash_attention_head_major(q, k, v, causal=True, block_q=1024,
                               block_k=1024, mesh=None, window=0,
                               scale=None):
    """:func:`flash_attention` for operands that arrive as the kernels
    take them (``ops/qk_prep.py`` writes them so): ``q`` [B, H, T, D],
    ``k``, ``v`` [B, Hkv, T, D] -> [B, T, H, D], with no transpose in
    front of the kernel. No bias; off the TPU the same reference math."""
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if not use_pallas("flash_attention", (q, k, v), _INTERPRET):
        return flash_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                               causal=causal, window=window, scale=scale)
    return _over_mesh(
        functools.partial(_head_major, causal=causal, block_q=block_q,
                          block_k=block_k, window=window, scale=scale),
        mesh, (q, k, v), 1)


def flash_attention(q, k, v, causal=True, kv_bias=None, block_q=1024,
                    block_k=1024, mesh=None, window=0, scale=None):
    """Flash attention. q,k,v: [B, T, H, D] (framework layout; kv heads
    may be fewer — GQA is handled natively: the kernels index kv-head
    ``query_head // n_rep``, so the expansion never materializes in
    HBM). Returns [B, T, H, D].

    ``kv_bias`` is an optional [B, Tk] f32 additive per-key bias —
    padding masks pass 0 for real keys and a large negative for padding
    (BERT-style bidirectional attention over ragged batches). It is
    treated as a CONSTANT (stop_gradient on every path): masks have no
    useful gradient, and the TPU kernel does not compute one.

    ``window`` W > 0 (causal calls without a bias only): key j is
    visible to query i where ``i - W < j <= i``, a band under the
    diagonal (sliding-window attention). The kernels skip the tiles
    wholly below the band as they skip those above the diagonal (no DMA,
    no matmul), mask the tiles an edge crosses and run the rest bare;
    ``tile_counts`` says how many of each. 0: every earlier key.

    ``v`` may be another width than ``q`` and ``k`` ([B, T, Hkv, Dv]:
    latent attention's 192 beside 128); the result is [B, T, H, Dv].
    ``scale`` multiplies the scores (calls without a bias only); None:
    ``1 / sqrt(D)`` of the queries' width.

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
    if window and (not causal or kv_bias is not None):
        raise ValueError("flash_attention: a window needs causal=True "
                         "and no kv_bias")
    if kv_bias is not None and (scale is not None
                                or v.shape[-1] != q.shape[-1]):
        raise ValueError("flash_attention: a kv_bias call takes no scale "
                         "and one width for q, k and v")
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

        return checkpoint_name(
            blockwise_attention(q, k, v, causal=causal, window=window,
                                scale=scale),
            "attn_out")

    def kernel(q, k, v, kv_bias=None):
        return _head_major(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                           kv_bias, causal=causal, block_q=block_q,
                           block_k=block_k, window=window, scale=scale)

    return _over_mesh(kernel, mesh,
                      (q, k, v) if kv_bias is None else (q, k, v, kv_bias), 2)
