"""Dropless sorted grouped-GEMM mixture-of-experts FFN (TPU-first).

Why: the GShard dispatch path (``models/llama.py:_moe_ffn``) pays two
structural taxes on a single chip:

1. the one-hot dispatch/combine einsums ``btec,btd->becd`` /
   ``btec,becd->btd`` are real matmuls — at bench shape (B4 T2048 E4
   C1280 D2048) they cost ~2x86 GFLOP/layer against ~1030 GFLOP for the
   expert FFN itself (a ~17% pure-overhead FLOP tax), and
2. capacity-factor padding makes the expert GEMMs compute E*C =
   T*K*capacity_factor token-slots instead of the T*K that carry
   tokens (+25% at cf=1.25) — waste that active-param MFU accounting
   charges straight to the implementation.

This path removes both: flatten the (token, k) slots, ``argsort`` them
by routed expert (64K int32 keys: microseconds), gather the activation
rows once into expert order, and run the three expert projections as
ragged grouped matmuls (``jax.experimental.pallas.ops.tpu.megablox.gmm``
— measured at dense-matmul throughput on v5e). Every token-slot is
computed — no capacity, no dropped tokens (dropless), no padding FLOPs.

How the routed rows move: the gate weight of a slot scales its activation row BEFORE the down projection, so the
way back to token order is a plain sum of each token's K rows. Dispatch
(one gather from the [S, D] tokens) and combine (one permutation gather
of [S*K, D], reduced by K at once) are each other's transpose and each
other's custom VJP: no XLA scatter on the hot path, and no residual in
slot order. The slots are counted by a fused compare-and-sum and sorted
twice a layer (the order, which the gate weights ride, and its inverse,
handed to every VJP), and every gather promises the bounds its
construction guarantees. No row kernel: Mosaic's DMAs address a 2-D
bf16 array in HBM by tiles of 8 rows (a row lies interleaved with its
pair in 16 pieces), so a kernel cannot fetch one routed row; XLA's
gather can (readings: PERF.md section 6).

A share of the experts: a program that is ONE chip of an
expert-parallel deployment (``LlamaConfig.first_expert``,
``n_experts_held``) routes over all experts and computes the chosen
ones it holds, without the exchange (``_held_experts_ffn``): the slots
of absent experts sort last, the held rows are worked off in chunks of
a static length, and a token's rows come back by a scatter-add, since
most of a token's K slots are elsewhere. A chunk's buffers are as long
as the chunk; its rows are GATHERED block by block and only as far as
the held rows reach (``held_blocks``: a gather costs by the row,
whoever reads it), as the grouped GEMMs visit the tiles of their groups
and nothing else. The scatter-add stays one call a chunk: in blocks it
costs three times as much a row (``_sum_held``).

Sharding: this path is for programs where the experts are NOT sharded
over an ``expert`` mesh axis (single chip, or EP-free meshes) — the
sort is a per-program global op. Expert-parallel meshes keep the GShard
grouped-einsum path, whose [G, E, C, D] buffers give GSPMD the clean
all-to-all seam (``LlamaConfig.moe_impl`` documents the dispatch).

Reference analog: none (Horovod has no model layer); the design follows
the public dropless-MoE formulation (MegaBlocks) re-founded on TPU
primitives.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.utils.spans import scope

# Megablox tile sizes (m, k, n), clamped to the problem dims. Swept on
# a v5e chip at bench shape (m=16K, D=2048, F=4096): large k/n tiles
# beat the (128,128,128) default by ~2x; m=512 keeps the ragged group
# boundaries cheap. The three directions get INDEPENDENT tilings —
# megablox's stock custom_vjp reuses the forward tiling for dlhs and
# tgmm, so one direction's compiler ceiling caps all three. (All three
# sit at the shared optimum of the r5 sweep, which could not compile
# tiles > 1024; larger tiles have not been tried on libtpu 0.0.34.
# Gradient parity with the stock VJP was pinned on the r5 chip — see
# docs/benchmarks.md.)
_TILING = (512, 1024, 1024)          # forward gmm
_TILING_DLHS = (512, 1024, 1024)     # backward dlhs gmm (transposed rhs)
_TILING_TGMM = (512, 1024, 1024)     # backward dW tgmm


def _rows(x, idx):
    """``x[idx]`` along axis 0 for an index that is in bounds BY
    CONSTRUCTION (a permutation, or ``order // K``): the bare gather.
    ``jnp.take``'s default ``mode="fill"`` lowers to an in-bounds mask
    over the indices and a select against NaN over the whole result."""
    return x.at[idx].get(mode="promise_in_bounds")


# The routed rows move between token order [S, D] and expert order
# [S*K, D] through two linear maps, each the other's transpose, so each
# is the other's VJP and no XLA scatter ever appears on the hot path:
#
#   dispatch  G(h)[i] = h[tok[i]]               tok = order // K
#   combine   C(z)[t] = sum_k z[inv[t, k]]      inv = argsort(order)
#
# ``tok`` [S*K] and ``inv`` [S, K] are data (int32; their cotangent is
# the symbolic zero). Both directions hand BOTH to the other, so the
# inverse permutation is sorted once, in the forward.

@jax.custom_vjp
@scope("hvd.moe.dispatch")
def _dispatch(h, tok, inv):
    """Rows of ``h`` [S, D] replicated K ways into expert order
    [S*K, D] in ONE gather."""
    return _rows(h, tok)


def _dispatch_fwd(h, tok, inv):
    return _dispatch(h, tok, inv), (tok, inv)


def _dispatch_bwd(res, g):
    return _combine(g, *res), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
@scope("hvd.moe.combine")
def _combine(z, tok, inv):
    """The K expert-order rows of each token summed back into token
    order [S, D]: the rows are read where they lie (a permutation
    gather) and reduced by K with f32 accumulation."""
    S, K = inv.shape
    rows = _rows(z, inv.reshape(S * K))
    return rows.reshape(S, K, -1).sum(axis=1, dtype=jnp.float32).astype(
        z.dtype)


def _combine_fwd(z, tok, inv):
    return _combine(z, tok, inv), (tok, inv)


def _combine_bwd(res, g):
    return _dispatch(g, *res), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _clamp(tiling, m, k, n):
    tm, tk, tn = tiling
    return (min(tm, m), min(tk, k), min(tn, n))


def _bwd_tilings(m, k, n):
    """Per-direction backward tilings clamped against EACH matmul's own
    (rows, contraction, out) dims — NOT the forward's (m, k, n).

    - dlhs runs ``gmm(grad [m,n], rhs [E,k,n], transpose_rhs=True)``:
      gmm reads its problem dims as (m, lhs.shape[1], rhs.shape[1]) =
      (m, n, k) — contraction over n, output k;
    - tgmm runs ``tgmm(lhs^T [k,m], grad [m,n])``: its (m, k, n) are
      (lhs.shape[1], lhs.shape[0], rhs.shape[1]) = (m, k, n), which
      COINCIDES with the forward dims (the contraction is over m, which
      tm tiles).

    Clamping dlhs against the forward dims handed it a tile larger than
    its real contraction/output whenever k and n straddle the 1024 tile
    boundary (d_model < 1024 <= d_ff — the gate/up projections'
    backward; ADVICE r5). Shapes pinned by
    tests/single/test_grouped_moe.py::test_bwd_tilings_clamp_per_direction.
    """
    return (_clamp(_TILING_DLHS, m, n, k),   # dlhs: (m, n, k)
            _clamp(_TILING_TGMM, m, k, n))   # tgmm: (m, k, n)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LayerOfStack:
    """Layer ``layer`` of expert matrices stacked ``[L, E, K, N]``, NOT
    sliced out: what an unrolled layer stack (``models/llama.py:
    _run_layers``) hands an expert layer in the place of ``stack[layer]``.
    A Mosaic custom call is opaque to fusion, so a slice in front of it
    is a copy of its own (268 MB a matrix at OLMoE's widths, 1.6 ms);
    ``_grouped_mm`` hands the kernel the whole stack and the kernel
    reads its tiles where they lie."""
    stack: jax.Array
    layer: int = dataclasses.field(metadata=dict(static=True))

    def astype(self, dtype):
        """The stack as it is where it already has ``dtype``; else the
        slice, converted (one fused pass that the conversion needs
        anyway; the whole stack converted would live a whole step)."""
        if self.stack.dtype == dtype:
            return self
        return self.stack[self.layer].astype(dtype)


def _one_layers_groups(stack, group_sizes, layer):
    """``stack`` [L, E, K, N] as the L*E groups of ONE grouped matmul in
    which only ``layer``'s E groups hold rows: megablox builds no tile
    for an empty group (``visit_empty_groups=False``) and reads ``rhs``
    at the group's index, so the kernel's DMAs fetch layer ``layer``'s
    tiles straight out of the stack (the reshape is a bitcast)."""
    L, E = stack.shape[:2]
    sizes = jnp.pad(group_sizes, (layer * E, (L - 1 - layer) * E))
    return stack.reshape(L * E, *stack.shape[2:]), sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_tpu(lhs, stack, group_sizes, layer):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    n = stack.shape[-1]
    return gmm(lhs, *_one_layers_groups(stack, group_sizes, layer),
               preferred_element_type=lhs.dtype,
               tiling=_clamp(_TILING, m, k, n))


def _gmm_tpu_fwd(lhs, stack, group_sizes, layer):
    return (_gmm_tpu(lhs, stack, group_sizes, layer),
            (lhs, stack, group_sizes))


def _gmm_tpu_bwd(layer, res, grad):
    # Same decomposition as megablox's stock VJP (ops.py), but each
    # direction gets its own tiling: dlhs = grad @ rhs^T via gmm with
    # transpose_rhs, dW via the transposed-lhs tgmm kernel. tgmm zeroes
    # an empty group's output, so it gets this layer's E groups alone
    # and the stack's gradient is its result padded: summed over the
    # layers XLA writes each stacked gradient in ONE pass
    # (``pad_add_fusion``).
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, stack, group_sizes = res
    m, k = lhs.shape
    n = stack.shape[-1]
    dlhs_tiling, tgmm_tiling = _bwd_tilings(m, k, n)
    dlhs = gmm(grad, *_one_layers_groups(stack, group_sizes, layer),
               lhs.dtype, dlhs_tiling, transpose_rhs=True)
    drhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, stack.dtype,
                tgmm_tiling)
    others = [(layer, stack.shape[0] - 1 - layer)] + [(0, 0)] * 3
    return dlhs, jnp.pad(drhs[None], others), None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


@scope("hvd.moe.experts")
def _grouped_mm(lhs, rhs, group_sizes):
    """Ragged grouped matmul: rows of ``lhs`` [M, K] are grouped
    contiguously per ``group_sizes`` [E]; ``rhs`` [E, K, N], or a
    :class:`LayerOfStack` of such. On TPU this
    is the megablox pallas kernel (dense-matmul throughput, f32
    accumulation) under our per-direction-tiling custom VJP. Off-TPU
    tests use an exact one-hot einsum (tiny shapes only)."""
    of_stack = isinstance(rhs, LayerOfStack)
    if use_pallas("grouped_moe", (lhs, rhs.stack if of_stack else rhs)):
        stack, layer = (rhs.stack, rhs.layer) if of_stack else (rhs[None], 0)
        return _gmm_tpu(lhs, stack, group_sizes, layer)
    if of_stack:
        rhs = rhs.stack[rhs.layer]
    # Exact reference: expert id per row from the group layout, then a
    # one-hot contraction (f32-exact; O(M*E*K*N) — test shapes only).
    eid = jnp.sum(jnp.arange(lhs.shape[0])[:, None]
                  >= jnp.cumsum(group_sizes)[None, :], axis=1)
    sel = jax.nn.one_hot(eid, rhs.shape[0], dtype=lhs.dtype)
    return jnp.einsum("se,sk,ekn->sn", sel, lhs, rhs)


@scope("hvd.moe.dispatch")
def _sort_slots_impl(e_flat, w):
    iota = lax.iota(jnp.int32, e_flat.shape[0])
    _, order, w_sorted = lax.sort((e_flat, iota, w), num_keys=1,
                                  is_stable=True)
    inv = jnp.argsort(order).astype(jnp.int32)
    return jnp.stack([order, inv]), w_sorted


@jax.custom_vjp
def _sort_slots(e_flat, w):
    """Sort the (token, k) slots by routed expert ``e_flat`` [S*K].
    Returns ``(order, inv)`` stacked [2, S*K] int32 and the slots' gate
    weights ``w`` [S*K] in that order: ``order`` lists the slots expert
    by expert (stable: token order within an expert), ``inv =
    argsort(order)`` says where each slot went. The weights ride the
    first sort as a payload (cheaper than a gather of scalars); their
    VJP is the gather by ``inv``."""
    return _sort_slots_impl(e_flat, w)


def _sort_slots_fwd(e_flat, w):
    # Named HERE, so that the VJP's residual is the named value and a
    # remat policy that saves these names ("attn+moe", "moe") re-runs
    # no sort in the backward.
    perm, w_sorted = _sort_slots_impl(e_flat, w)
    perm = checkpoint_name(perm, "moe_perm")
    return (perm, checkpoint_name(w_sorted, "moe_w_sorted")), perm[1]


@scope("hvd.moe.dispatch")
def _sort_slots_bwd(inv, g):
    return None, _rows(g[1], inv)


_sort_slots.defvjp(_sort_slots_fwd, _sort_slots_bwd)


@scope("hvd.moe.dispatch")
def _group_sizes(e_flat, n_experts):
    """Slots per expert [E] int32, empty experts included: a fused
    compare-and-reduce over [S*K, E], exact in int32 (``bincount`` is a
    scatter-add of S*K ones)."""
    experts = jnp.arange(n_experts, dtype=e_flat.dtype)
    return jnp.sum(e_flat[:, None] == experts, axis=0, dtype=jnp.int32)


# A program that holds a SHARE of the experts (``c.n_experts_held``)
# moves rows in chunks of this many times the slots an even router would
# hand it: one chunk at any load up to that, more only when more slots
# land here.
_HELD_ROW_BOUND = 2


# The share's row gathers run over blocks of this many rows of a chunk
# and stop after the last block that held rows reach (``held_blocks``):
# XLA's gather costs by the ROW (33 ns from HBM at 2048 columns of
# bf16; in blocks 28, and 10 more to write a block into its place), so
# a chunk half full is gathered in 0.6 of the time. One expert layer of
# the LFM2 cell, forward and backward, 16,732 rows held of a chunk of
# 32,768 (my chip runs, PR 35): 22.94 ms at 1024, 22.85 at 2048, 23.01
# at 4096, 23.80 with the whole chunk gathered; at 65,536 rows held (two
# chunks full) 60.63, 60.26, 60.18 and 60.19. A chunk whose length it
# does not divide is gathered as one block.
_HELD_BLOCK = 2048


def held_blocks(n, start, chunk_rows, block_rows):
    """How many blocks of ``block_rows`` rows the share's gathers visit
    in the chunk of sorted slots ``start .. start + chunk_rows - 1`` of
    a layer that holds ``n`` rows: those up to the last one a held row
    lies in, ``ceil(clip(n - start, 0, chunk_rows) / block_rows)``.
    Over ``chunk_rows // block_rows`` it is the share of the chunk that
    is gathered (``docs/metrics.md``)."""
    return (jnp.clip(n - start, 0, chunk_rows) + block_rows - 1) // block_rows


def _block_rows(chunk_rows):
    return _HELD_BLOCK if chunk_rows % _HELD_BLOCK == 0 else chunk_rows


@scope("hvd.moe.dispatch")
def _gather_held(x, tok, held):
    """``x[tok]`` [R, D] for the first ``held`` of ``tok``'s R rows,
    gathered block by block into place. Rows past the last visited block
    are NOT WRITTEN (``lax.empty``: on the chip whatever the buffer held,
    NaN for all anyone knows), those between ``held`` and its end are
    rows nobody asked for: no group of the grouped GEMMs covers either,
    and every sum over a chunk's rows selects by ``held`` first."""
    R = tok.shape[0]
    B = _block_rows(R)
    shape = (R, x.shape[1])

    def block(i, out):
        rows = _rows(x, lax.dynamic_slice_in_dim(tok, i * B, B))
        return lax.dynamic_update_slice_in_dim(out, rows, i * B, 0)

    # The buffer is born inside a branch: the compiler allocates a
    # buffer that depends on nothing at the program's start, and the
    # layers' 24 of them (134 MB each at the cells' sizes) then live
    # all at once. A zero-fill in its place is a pass of its own.
    return lax.cond(
        held > 0,
        lambda: lax.fori_loop(0, held_blocks(held, 0, R, B), block,
                              lax.empty(shape, x.dtype)),
        lambda: jnp.zeros(shape, x.dtype))


@scope("hvd.moe.combine")
def _sum_held(rows, tok, held, n_tokens):
    """``out[t] = sum of rows[i] where tok[i] == t and i < held``,
    [n_tokens, D] in ``rows``' dtype, accumulated in float32 (a
    scatter-add: a token has 0..K of the chunk's rows, in no order).
    What ``rows`` holds from ``held`` on (the grouped GEMMs leave it
    UNWRITTEN) is selected away, never multiplied: it may be NaN.

    All R rows in ONE scatter-add, whatever ``held``: the compiler
    sorts the indices, permutes the rows and sums sorted runs in one
    fusion at 75 ns a row, and each call passes over the whole
    accumulator first. Block by block as ``_gather_held`` it reads 245
    ns a row, 4.6 ms where this takes 3.2 (PERF.md section 6, PR 35)."""
    live = lax.iota(jnp.int32, tok.shape[0]) < held
    out = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32)
    return out.at[tok].add(
        jnp.where(live[:, None], rows, 0).astype(jnp.float32),
        mode="promise_in_bounds").astype(rows.dtype)


# The share's two row movements, each the other's transpose and VJP as
# ``_dispatch`` / ``_combine`` are, over one chunk of R sorted slots of
# which the first ``held`` are slots of experts held here. The gather's
# loop has a trip count that follows ``held`` (a ``while``): legal
# because it lives inside custom VJPs, which are never differentiated
# again.

@jax.custom_vjp
def _dispatch_held(h, tok, held):
    return _gather_held(h, tok, held)


def _dispatch_held_fwd(h, tok, held):
    return _gather_held(h, tok, held), (tok, held, h.shape[0])


def _dispatch_held_bwd(res, g):
    tok, held, n_tokens = res
    return _sum_held(g, tok, held, n_tokens), None, None


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_held(z, tok, held, n_tokens):
    return _sum_held(z, tok, held, n_tokens)


def _combine_held_fwd(z, tok, held, n_tokens):
    return _sum_held(z, tok, held, n_tokens), (tok, held)


def _combine_held_bwd(n_tokens, res, g):
    return _gather_held(g, *res), None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def held_chunks(n, chunk_rows, chunks):
    """How many of a layer's ``chunks`` chunks of ``chunk_rows`` sorted
    slots run when it holds ``n`` rows: the first always, a later one
    only if a held row lies in it, ``clip(ceil(n / chunk_rows), 1,
    chunks)``. Less one it is the trip count of the later chunks' loop,
    forward and backward (``docs/metrics.md``)."""
    return jnp.clip((n + chunk_rows - 1) // chunk_rows, 1, chunks)


def _expert_matrices(lp, c):
    """An expert layer's matrices in the order :func:`_experts` takes
    them: ``(gate, up, down)``, or ``(up, down)`` of a ``relu2`` FFN,
    which has no gate leaf."""
    names = ("moe_gate", "moe_up", "moe_down")[c.ffn_act == "relu2":]
    return tuple(lp[name] for name in names)


def _experts(c, x_sorted, matrices, sizes, w):
    """The experts' FFN on rows in expert order ``x_sorted`` [M, d] with
    the groups ``sizes``, each row's activation scaled by its gate
    weight ``w`` [M] -> [M, d]: SwiGLU through three grouped GEMMs, or
    (``c.ffn_act`` "relu2") ``relu(x Wu)^2 Wd`` through two."""
    dt = c.compute_dtype
    *gate, up, down = matrices
    # Residual names for the "moe" remat mode (save the expert-GEMM
    # chain so backward re-runs NO grouped matmul): the PRE-silu gate
    # is what silu's vjp needs; up pairs with it for the product rule.
    if gate:
        gate_pre = checkpoint_name(
            _grouped_mm(x_sorted, gate[0].astype(dt), sizes), "moe_gate_act")
    up = checkpoint_name(
        _grouped_mm(x_sorted, up.astype(dt), sizes), "moe_up_act")
    with scope("hvd.moe.experts"):
        if gate:
            act = jax.nn.silu(gate_pre) * up * w[:, None]
        else:
            act = jnp.square(jax.nn.relu(up)) * w[:, None]
    return _grouped_mm(act, down.astype(dt), sizes)


def _held_chunk(c, R, hf, matrices, w_sorted, order, ends, n, start):
    """Sorted slots ``start .. start + R - 1`` of a share's layer
    (:func:`_held_experts_ffn`) -> their part of the sum, [S, d]."""
    S, K = hf.shape[0], c.n_experts_per_token
    tok = lax.dynamic_slice_in_dim(order, start, R) // K
    held = jnp.clip(n - start, 0, R)
    w = jnp.where(lax.iota(jnp.int32, R) < held,
                  lax.dynamic_slice_in_dim(w_sorted, start, R), 0)
    # each group's rows that fall inside this chunk
    sizes = jnp.diff(jnp.clip(ends, start, start + R), prepend=start)
    x_sorted = _dispatch_held(hf.astype(c.compute_dtype), tok, held)
    y_sorted = _experts(c, x_sorted, matrices, sizes, w)
    return _combine_held(y_sorted, tok, held, S)


# The chunks after the first: ``y`` plus each chunk that held rows
# reach, in chunk order. A loop whose trip count follows the rows held
# (``held_chunks``), forward and backward, so a chunk that no row
# reaches is no iteration at all: legal for the reason ``_gather_held``'s
# loop is, and the reason for the custom VJP (a ``lax.scan`` of a
# ``lax.cond``, differentiated, stacks every operand once an iteration
# as a residual and re-sums every cotangent, whether the chunk runs or
# not: 1.88 GB a layer of the Qwen3-Next cell, where none ever does;
# PERF.md section 6, PR 41). Nothing is saved but the operands, which
# live anyway; the backward runs each chunk again from them and pulls
# the cotangent through it, the last chunk first as a transposed scan
# would, into accumulators that start from zero: one zero cotangent an
# operand is what a layer still pays when no later chunk runs.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _later_chunks(c, R, y, operands, slots):
    order, _, n = slots

    def add(i, y):
        return y + _held_chunk(c, R, *operands, *slots, i * R)

    with scope("hvd.moe.combine"):   # the later chunks' sums join y
        return lax.fori_loop(1, held_chunks(n, R, order.shape[0] // R),
                             add, y)


def _later_chunks_fwd(c, R, y, operands, slots):
    return _later_chunks(c, R, y, operands, slots), (operands, slots)


def _later_chunks_bwd(c, R, res, g):
    operands, slots = res
    order, _, n = slots
    ran = held_chunks(n, R, order.shape[0] // R)

    def pull(i, cts):
        _, vjp = jax.vjp(
            lambda *ops: _held_chunk(c, R, *ops, *slots, (ran - i) * R),
            *operands)
        return jax.tree.map(jnp.add, cts, vjp(g))

    def zeros():
        return jax.tree.map(jnp.zeros_like, operands)

    # Born in a branch, as ``_gather_held``'s buffer is: zero-filled in
    # the entry computation the accumulators raised the LFM2 cell's peak
    # by 0.15 GB on the chip (its grad program as compiled for the
    # described v5e 7.39 GB where the parent's and this read 7.12).
    with scope("hvd.moe.combine"):
        cts = lax.cond(ran > 1,
                       lambda: lax.fori_loop(1, ran, pull, zeros()), zeros)
    return g, cts, None


_later_chunks.defvjp(_later_chunks_fwd, _later_chunks_bwd)


@scope("hvd.moe.dispatch")
def _held_experts_ffn(hf, lp, c, gate_vals, gate_idx):
    """The routed part of an expert layer that holds experts
    ``c.first_expert .. + c.n_experts_held - 1`` of ``c.n_experts``:
    ``sum over the chosen experts held here of w_k Expert_k(h)`` for
    the tokens' rows ``hf`` [S, d] (``d`` the width the experts work in:
    ``c.expert_d_in``), routed (``gate_vals``, ``gate_idx``
    [S, K]) over ALL experts. Dropless at any load: no slot of a held
    expert is left out.

    The slots are sorted by LOCAL expert with those of absent experts
    last, so the ``n`` held rows are the head of the order. They are
    worked off in chunks of R rows, R = ``_HELD_ROW_BOUND`` x the even
    share (S*K*held/E): gathered out of ``hf``, through the three
    grouped GEMMs (which visit the tiles of their groups and nothing
    else), summed back per token. The first chunk always runs, under
    plain autodiff; the others are a loop that runs as many times as
    held rows reach a further chunk (``held_chunks``; ``_later_chunks``,
    a custom VJP whose backward is the same loop), so every temporary
    is R rows long at any load and a chunk nobody's rows reach costs
    nothing. Within a chunk the gathers run over blocks of
    ``_HELD_BLOCK`` rows and stop after the last block a held row lies
    in (``held_blocks``): the layer gathers the rows it holds, rounded
    up to a block, not R. The scatter-adds take all R rows, those from
    the last held row on selected away (``_sum_held``)."""
    S, D = hf.shape
    K, H = c.n_experts_per_token, c.n_experts_held
    dt = c.compute_dtype
    local = lax.stop_gradient(gate_idx.reshape(S * K)) - c.first_expert
    key = jnp.where((local >= 0) & (local < H), local, H)
    (order, _), w_sorted = _sort_slots(
        key, gate_vals.astype(dt).reshape(S * K))
    ends = jnp.cumsum(_group_sizes(key, H))
    chunks = max(c.n_experts // (H * _HELD_ROW_BOUND), 1)
    if (S * K) % chunks:
        chunks = 1
    R = S * K // chunks
    operands = (hf, _expert_matrices(lp, c), w_sorted)
    slots = (order, ends, ends[-1])
    y = _held_chunk(c, R, *operands, *slots, 0)
    if chunks == 1:
        return y
    return _later_chunks(c, R, y, operands, slots)


def grouped_moe_ffn(h, lp, c, rows=None):
    """Dropless top-K routed expert FFN over ``h`` [B, T, D] with the
    layer params ``lp`` (router [D, E], moe_gate/moe_up [E, d, F],
    moe_down [E, F, d]; no moe_gate under ``c.ffn_act`` "relu2"). The
    router reads ``h``; the experts read ``rows`` [B, T, d] where given
    (latent experts, ``c.moe_latent``) and ``h`` where not, and the
    result is as wide as what they read. Returns (out [B, T, d],
    balance statistics) —
    the same contract, router math, gate normalization
    (``c.norm_topk_prob``) and load-balancing statistics as the GShard
    path (``models/llama.py:_moe_ffn``), with no capacity dropping
    (every token-slot is computed). With a share of the experts
    (``c.n_experts_held``; the expert matrices then hold those experts
    only) the router is the same and the result is the held experts'
    part: :func:`_held_experts_ffn`.
    """
    B, T, D = h.shape
    E, K = c.n_experts, c.n_experts_per_token
    S = B * T
    dt = c.compute_dtype
    hf = h.reshape(S, D)

    # Shared router (llama.moe_route): identical math and statistics to
    # the GShard path's (means over flat S == means over (B, T)).
    from horovod_tpu.models.llama import route_layer

    gate_vals, gate_idx, aux = route_layer(hf, lp, c)          # [S, K]
    if rows is not None:
        D = rows.shape[-1]
        hf = rows.reshape(S, D)
    if c.n_experts_held:
        y = _held_experts_ffn(hf, lp, c, gate_vals, gate_idx)
        return y.reshape(B, T, D), aux

    # Sort the S*K (token, k) slots by routed expert: two sorts, the
    # order (the gate weights ride it) and its inverse. Indices are
    # data (not differentiated); stop_gradient keeps the int chain out
    # of the autodiff graph entirely.
    with scope("hvd.moe.dispatch"):
        e_flat = lax.stop_gradient(gate_idx.reshape(S * K))
        (order, inv), w_sorted = _sort_slots(
            e_flat, gate_vals.astype(dt).reshape(S * K))
        tok, inv = order // K, inv.reshape(S, K)
        group_sizes = _group_sizes(e_flat, E)

    # Not named for any remat mode: gathering the rows again in the
    # backward (from h, a source XLA holds in VMEM) is cheaper than
    # saving them.
    x_sorted = _dispatch(hf.astype(dt), tok, inv)

    # The gate weight of each slot scales its ACTIVATION row (in the
    # compute dtype, in the elementwise pass that exists anyway), not
    # its output row: sum_k w * (a @ Wd) = sum_k (w * a) @ Wd. The
    # combine is then a plain sum of K rows, its backward a plain
    # gather of dy, and the router's gradient d w = <d(w * a), a> comes
    # out of the silu * up backward pass at width F: nothing is saved
    # in slot order, and the down-projection's output is no residual of
    # anything.
    y_sorted = _experts(c, x_sorted, _expert_matrices(lp, c), group_sizes,
                        w_sorted)                  # [S*K, D]
    y = _combine(y_sorted, tok, inv)
    return y.reshape(B, T, D), aux
