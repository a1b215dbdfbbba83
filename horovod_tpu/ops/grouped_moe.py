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
by routed expert (16K int32 keys — microseconds), gather the activation
rows once, and run the three expert projections as ragged grouped
matmuls (``jax.experimental.pallas.ops.tpu.megablox.gmm`` — measured at
dense-matmul throughput on v5e). Every token-slot is computed — no
capacity, no dropped tokens (dropless), no padding FLOPs. The
un-permutation is a custom-VJP gather whose backward is the inverse
gather, so no XLA scatter ever appears on the hot path.

Sharding: this path is for programs where the experts are NOT sharded
over an ``expert`` mesh axis (single chip, or EP-free meshes) — the
sort is a per-program global op. Expert-parallel meshes keep the GShard
grouped-einsum path, whose [G, E, C, D] buffers give GSPMD the clean
all-to-all seam (``LlamaConfig.moe_impl`` documents the dispatch).

Reference analog: none (Horovod has no model layer); the design follows
the public dropless-MoE formulation (MegaBlocks) re-founded on TPU
primitives.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops._platform import use_pallas

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _unpermute(x, perm, _n):
    """``x[perm]`` where ``perm`` is a PERMUTATION (bijective): the VJP
    is the gather by the inverse permutation — never an XLA scatter.
    ``perm`` rides as a regular traced operand; its cotangent is the
    symbolic zero for ints. ``_n`` is unused padding to keep the vjp
    signature stable (nondiff static)."""
    return jnp.take(x, perm, axis=0)


def _unpermute_fwd(x, perm, _n):
    return jnp.take(x, perm, axis=0), perm


def _unpermute_bwd(_n, perm, g):
    # inverse gather: out[perm[i]] = g[i]  <=>  out = g[argsort(perm)]
    return jnp.take(g, jnp.argsort(perm), axis=0), None


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_gather(h, slot_token, sorted_order, K):
    """Rows of ``h`` [S, D] replicated K ways and permuted into expert
    order in ONE gather: out[i] = h[slot_token[i]] ([S*K, D]).

    ``slot_token = sorted_order // K`` (token of each sorted slot). The
    VJP avoids a duplicate-index scatter: un-permute the cotangent back
    to (token, k) slot order with the inverse permutation, then sum the
    K slots of each token — a reshape + reduce.
    """
    return jnp.take(h, slot_token, axis=0)


def _dispatch_gather_fwd(h, slot_token, sorted_order, K):
    return jnp.take(h, slot_token, axis=0), sorted_order


def _dispatch_gather_bwd(K, sorted_order, g):
    flat = jnp.take(g, jnp.argsort(sorted_order), axis=0)  # slot order
    dh = flat.reshape(-1, K, g.shape[-1]).sum(axis=1)
    return dh, None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _gmm_tpu(lhs, rhs, group_sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    n = rhs.shape[-1]
    return gmm(lhs, rhs, group_sizes,
               preferred_element_type=lhs.dtype,
               tiling=_clamp(_TILING, m, k, n))


def _gmm_tpu_fwd(lhs, rhs, group_sizes):
    return _gmm_tpu(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_tpu_bwd(res, grad):
    # Same decomposition as megablox's stock VJP (ops.py), but each
    # direction gets its own tiling: dlhs = grad @ rhs^T via gmm with
    # transpose_rhs, dW via the transposed-lhs tgmm kernel.
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[-1]
    dlhs_tiling, tgmm_tiling = _bwd_tilings(m, k, n)
    dlhs = gmm(grad, rhs, group_sizes, lhs.dtype,
               dlhs_tiling, transpose_rhs=True)
    drhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                tgmm_tiling)
    return dlhs, drhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def _grouped_mm(lhs, rhs, group_sizes):
    """Ragged grouped matmul: rows of ``lhs`` [M, K] are grouped
    contiguously per ``group_sizes`` [E]; ``rhs`` [E, K, N]. On TPU this
    is the megablox pallas kernel (dense-matmul throughput, f32
    accumulation) under our per-direction-tiling custom VJP. Off-TPU
    tests use an exact one-hot einsum (tiny shapes only)."""
    if use_pallas("grouped_moe", (lhs, rhs)):
        return _gmm_tpu(lhs, rhs, group_sizes)
    # Exact reference: expert id per row from the group layout, then a
    # one-hot contraction (f32-exact; O(M*E*K*N) — test shapes only).
    eid = jnp.sum(jnp.arange(lhs.shape[0])[:, None]
                  >= jnp.cumsum(group_sizes)[None, :], axis=1)
    sel = jax.nn.one_hot(eid, rhs.shape[0], dtype=lhs.dtype)
    return jnp.einsum("se,sk,ekn->sn", sel, lhs, rhs)


def grouped_moe_ffn(h, lp, c):
    """Dropless top-K routed expert FFN over ``h`` [B, T, D] with the
    layer params ``lp`` (router [D, E], moe_gate/moe_up [E, D, F],
    moe_down [E, F, D]). Returns (out [B, T, D], balance statistics) —
    the same contract, router math, gate normalization
    (``c.norm_topk_prob``) and load-balancing statistics as the GShard
    path (``models/llama.py:_moe_ffn``), with no capacity dropping
    (every token-slot is computed).
    """
    B, T, D = h.shape
    E, K = c.n_experts, c.n_experts_per_token
    S = B * T
    dt = c.compute_dtype
    hf = h.reshape(S, D)

    # Shared router (llama.moe_route): identical math and statistics to
    # the GShard path's (means over flat S == means over (B, T)).
    from horovod_tpu.models.llama import moe_route

    gate_vals, gate_idx, aux = moe_route(hf, lp["router"], K,
                                         c.norm_topk_prob)     # [S, K]

    # Sort the S*K (token, k) slots by routed expert. Indices are data
    # (not differentiated); stop_gradient keeps the int chain out of
    # the autodiff graph entirely.
    e_flat = lax.stop_gradient(gate_idx.reshape(S * K))
    order = jnp.argsort(e_flat)                    # sorted slot -> slot
    group_sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)

    # Residual names for the "moe" remat mode (save the expert-GEMM
    # chain so backward re-runs NO grouped matmul): x_sorted is the
    # tgmm lhs for dW_gate/dW_up; the PRE-silu gate is what silu's vjp
    # needs; up pairs with it for the product rule.
    x_sorted = checkpoint_name(
        _dispatch_gather(hf.astype(dt), order // K, order, K),
        "moe_x_sorted")

    gate_pre = checkpoint_name(
        _grouped_mm(x_sorted, lp["moe_gate"].astype(dt), group_sizes),
        "moe_gate_act")
    up = checkpoint_name(
        _grouped_mm(x_sorted, lp["moe_up"].astype(dt), group_sizes),
        "moe_up_act")
    y_sorted = _grouped_mm(jax.nn.silu(gate_pre) * up,
                           lp["moe_down"].astype(dt),
                           group_sizes)            # [S*K, D]

    # Un-permute to slot order (inverse-gather VJP) and combine with
    # the gate weights. Named for the "attn+moe" remat mode:
    # the router's combine-weight gradient needs y_slots (d gate_vals =
    # <dy, y_slots>), which is what forces the backward remat to re-run
    # the down-projection gmm — saving it trades [S*K, D] bf16 per
    # layer for that re-run.
    y_slots = checkpoint_name(
        _unpermute(y_sorted, jnp.argsort(order), S * K), "moe_y_slots")
    y = (y_slots.reshape(S, K, D)
         * gate_vals.astype(dt)[..., None]).sum(axis=1)
    return y.reshape(B, T, D), aux
