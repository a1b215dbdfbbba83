"""The seam between an attention layer's q/k/v projections and the flash
kernels (``models/llama.py:mix``), ONE pass over HBM forward and one
backward: the per-head q/k RMSNorm, RoPE and the split into heads.

From ``yq`` [B, T, H d], ``yk``, ``yv`` [B, T, Hkv d] as ``h @ wq``,
``h @ wk``, ``h @ wv`` leave them (no reshape into heads in front: read
as ``[B, T, H, d]`` the same bytes are tiled with the heads on the
sublanes, a copy of the whole array on a TPU, ``ops/gdn_chain.py``), to
``q`` [B, H, T, d], ``k``, ``v`` [B, Hkv, T, d] as ``_flash`` takes
them. What the expressions ``_head_proj``'s norm a head, ``_rope`` and
``flash_attention``'s transposes do in five passes or more, and what
the kernels are tested against.

A grid step ``(batch, block of tokens)`` holds its tokens at full width
and walks the heads, each a slab of ``d`` lanes: the head's mean square
and ``rsqrt`` in float32, the rounding to the compute dtype and the
gain where ``_rms`` has them, the half-split
rotation as one lane roll by ``d / 2`` and two multiply-adds in float32
(ONE rounding where ``_rope`` has three), and the store into the output
block ``[heads, tokens, d]``: the block's index carries the head, so the
transpose costs nothing. ``v`` is a relayout only. The rotation's table
``[B, T, d]`` float32 holds ``[cos, sin]`` of a token's angles side by
side and is made outside from the positions; a layer without RoPE hands
in ``[1, 0]`` and a model without a q/k norm a gain of None, so that a
program lowers two kernels whatever its layers:

- ``hvd_qk_prep_fwd``: grid ``(B, T / bt)``, both parallel.
- ``hvd_qk_prep_bwd``: the same grid; takes ``dq``, ``dk``, ``dv``
  head-major as the flash backward leaves them and (with a norm) ``yq``,
  ``yk`` again, writes ``dyq``, ``dyk``, ``dyv`` in ``[B, T, H d]``, what
  the projections' backward matmuls read, and a sublane tile of partial
  sums of each gain's gradient a grid step, summed outside.

Behind a ``custom_vjp`` that saves its operands and nothing else (under
remat the projections are re-run anyway). The names are the calls'
``kernel_metadata``, what a device trace shows; each kernel sits behind
ONE jitted function, so a program pays one Mosaic lowering a kernel
whatever the number of layers (``ops/gated_delta_rule.py`` says why).
:func:`on_kernels` reads the carrier off the operands
(``ops/_platform.py``); elsewhere the caller's expressions run.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import _kernel_mesh_specs, _pick_block
from horovod_tpu.ops.gdn_chain import _partial_sums
from horovod_tpu.utils.spans import scope

F32 = jnp.float32
# Tests flip this to run the kernels in pallas interpret mode on the CPU
# (as ``flash_attention._INTERPRET``).
_INTERPRET = False
# What one grid step takes: so many tokens (or the largest divisor of
# the sequence under them) at full width. Trinity-Mini's seam forward /
# forward + backward on the v5e, ten calls by the host's clock (which
# reads a third over the device trace), ms: 128 tokens 0.98 / 1.08, 256
# 0.69 / 0.90, 512 0.64 / 1.22, 1024 0.65 / 0.91 at four times the
# compile; walked 32 tokens a pass inside a step 2.29 / 2.57 (PERF.md,
# PR 48).
TOKENS_A_STEP = 256
# A trip of the loop over heads takes so many (or the largest divisor of
# their number under it): the kernel's text, and the time to lower it
# that every run pays, grows with the heads a trip and not with their
# number. Trinity-Mini's seam forward / backward by the device trace, ms
# a call, and tracing + lowering the pair on the chip's host, s: 1 head
# a trip 0.649 / 0.776, 0.23; 2 0.576 / 0.686, 0.27; 4 0.538 / 0.668,
# 0.42; 8 0.523 / 0.671, 0.56; all 32 unrolled 0.497 / 0.675, 1.20
# (PERF.md, PR 48).
HEADS_A_TRIP = 4
_LANES = 128         # a head is whole slabs of them
_PACKED = 16         # rows of a packed bfloat16 tile
_SUBLANES = 8        # rows of a float32 tile, and of a partial sum


def on_kernels(x, head_dim, normed, turns, seq_parallel):
    """True where the seam runs as the kernel pair: the layer's input
    ``x`` [B, T, D] on a TPU (or ``_INTERPRET``, the tests' switch),
    whole packed tiles of tokens, heads of whole 128-lane slabs, a
    rotation of the whole head or none (``turns``: how many of a head's
    dimensions RoPE turns in this layer), something to fuse (a norm a
    head, ``normed``, or the rotation) and no sequence axis in play
    (``seq_parallel``: ring and Ulysses take ``[B, T, H, d]``)."""
    return (not seq_parallel and head_dim % _LANES == 0
            and x.shape[1] % _PACKED == 0
            and turns in (0, head_dim) and bool(turns or normed)
            and use_pallas("qk_prep", (x,), _INTERPRET))


def rotation_table(positions, theta, d):
    """``positions`` [B, T] -> float32 [B, T, d]: ``[cos, sin]`` of each
    token's ``d / 2`` angles, ``_rope``'s frequencies. ``theta`` None:
    the rotation that turns nothing, ``[1, 0]``."""
    if theta is None:
        one = jnp.arange(d) < d // 2
        return jnp.broadcast_to(one.astype(F32), (*positions.shape, d))
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    angles = positions[:, :, None].astype(F32) * freqs
    return jnp.concatenate([jnp.cos(angles), jnp.sin(angles)], -1)


def _swap(x):
    """A head's two halves change places."""
    return pltpu.roll(x, x.shape[-1] // 2, 1)


def _tables(table):
    """A block's ``[cos, sin]`` -> ``[cos, cos]``, ``[-sin, sin]``: the
    rotation is ``x * [cos, cos] + swap(x) * [-sin, sin]``, its
    transpose ``g * [cos, cos] - swap(g) * [-sin, sin]``."""
    first = lax.broadcasted_iota(jnp.int32, table.shape, 1) \
        < table.shape[1] // 2
    swapped = _swap(table)
    return jnp.where(first, table, swapped), \
        jnp.where(first, -swapped, table)


def _normed(x_ref, at, gain, eps):
    """``_rms`` on a head's slab of a block: (``x / rms`` in float32,
    ``1 / rms``, that product rounded, times the gain rounded), the
    rounded ones at the compute dtype's values in float32."""
    dt = x_ref.dtype
    x = x_ref[:, at].astype(F32)
    rs = lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    u = x * rs
    n = u.astype(dt).astype(F32)
    return u, rs, n, (n * gain).astype(dt).astype(F32)


def _walk(heads, turn, carry):
    """``turn(head, carry) -> carry`` over the heads, ``HEADS_A_TRIP`` of
    them unrolled a trip of a loop (Mosaic unrolls a loop whole or not
    at all, so the trip is written out here)."""
    a_trip = max(n for n in range(1, HEADS_A_TRIP + 1) if heads % n == 0)

    def trip(i, carry):
        for j in range(a_trip):
            carry = turn(i * a_trip + j, carry)
        return carry

    return lax.fori_loop(0, heads // a_trip, trip, carry)


def _slab(j, d):
    """Head ``j``'s lanes of a block ``[tokens, heads * d]``: whole
    128-lane slabs, which is what lets ``j`` be a loop's counter."""
    return pl.ds(pl.multiple_of(j * d, _LANES), d)


def _fwd_kernel(*refs, d, eps, norm):
    """``refs``: ``yq``, ``yk``, ``yv``, with a norm the two gains, the
    table; ``q``, ``k``, ``v`` out. A loop over the heads of each
    (``_walk``)."""
    yq_ref, yk_ref, yv_ref = refs[:3]
    gains = [r[...].astype(F32) for r in refs[3:5]] if norm else [None] * 2
    table_ref, q_ref, k_ref, v_ref = refs[-4:]
    cos, sin = _tables(table_ref[...])

    def prepare(y_ref, gain, out_ref):
        def turn(j, carry):
            y = _normed(y_ref, _slab(j, d), gain, eps)[3] if norm \
                else y_ref[:, _slab(j, d)].astype(F32)
            out_ref[j] = (y * cos + _swap(y) * sin).astype(out_ref.dtype)
            return carry

        _walk(out_ref.shape[0], turn, 0)

    def move(j, carry):
        v_ref[j] = yv_ref[:, _slab(j, d)]
        return carry

    prepare(yq_ref, gains[0], q_ref)
    prepare(yk_ref, gains[1], k_ref)
    _walk(v_ref.shape[0], move, 0)


def _bwd_kernel(*refs, d, eps, norm):
    """``refs``: with a norm ``yq``, ``yk`` and the two gains first; then
    ``dq``, ``dk``, ``dv``, the table; ``dyq``, ``dyk``, ``dyv`` out and
    with a norm the gains' partial sums."""
    ys, gains = (refs[:2], [r[...].astype(F32) for r in refs[2:4]]) \
        if norm else ([None] * 2, [None] * 2)
    dq_ref, dk_ref, dv_ref, table_ref, dyq_ref, dyk_ref, dyv_ref = \
        refs[4 * norm:4 * norm + 7]
    cos, sin = _tables(table_ref[...])

    def back(d_ref, y_ref, gain, out_ref):
        """-> a sublane tile of the gain's gradient's partial sums."""
        def turn_back(j, total):
            g = d_ref[j].astype(F32)
            dy = g * cos - _swap(g) * sin
            if norm:
                u, rs, n, _ = _normed(y_ref, _slab(j, d), gain, eps)
                total = total + _partial_sums(dy * n)
                du = dy * gain
                dy = rs * (du - u * jnp.mean(du * u, -1, keepdims=True))
            out_ref[:, _slab(j, d)] = dy.astype(out_ref.dtype)
            return total

        return _walk(d_ref.shape[0], turn_back,
                     jnp.zeros((_SUBLANES, d), F32))

    def move_back(j, carry):
        dyv_ref[:, _slab(j, d)] = dv_ref[j]
        return carry

    dgq = back(dq_ref, ys[0], gains[0], dyq_ref)
    dgk = back(dk_ref, ys[1], gains[1], dyk_ref)
    if norm:
        refs[-2][...], refs[-1][...] = dgq, dgk
    _walk(dv_ref.shape[0], move_back, 0)


def _specs(B, T, widths, d, bt):
    """(grid, the blocks of ``[B, T, heads d]`` a width, the blocks of
    ``[B, heads, T, d]`` a width, the table's block)."""
    flat = [pl.BlockSpec((None, bt, w), lambda b, t: (b, t, 0))
            for w in widths]
    heads = [pl.BlockSpec((None, w // d, bt, d), lambda b, t: (b, 0, t, 0))
             for w in widths]
    return (B, T // bt), flat, heads, \
        pl.BlockSpec((None, bt, d), lambda b, t: (b, t, 0))


def _call(name, kernel, operands, grid, in_specs, out_specs, out_shape,
          block_bytes, interpret):
    """``metadata`` is the name a device trace shows of the call
    (``ops/flash_attention.py:_pallas_dispatch``). The kernel asks for
    the VMEM its blocks take (the pipeline holds each twice) and room
    for a head's values."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=2 * block_bytes + (8 << 20)),
    )(*operands)


def _gain_spec(d):
    return pl.BlockSpec((1, d), lambda b, t: (0, 0))


@functools.partial(jax.jit, static_argnames=("eps", "bt", "interpret"))
def _fwd(yq, yk, yv, gq, gk, table, *, eps, bt, interpret):
    """-> ``q`` [B, H, T, d], ``k``, ``v`` [B, Hkv, T, d]. Jitted on its
    own, and the scope again, as ``gated_delta_rule._kernel_fwd`` has it
    and says why."""
    with scope("hvd.attn.rope"):
        B, T, d = table.shape
        widths = [y.shape[2] for y in (yq, yk, yv)]
        grid, flat, heads, tab = _specs(B, T, widths, d, bt)
        norm = gq is not None
        gains = [g[None] for g in (gq, gk)] if norm else []
        return _call(
            "hvd_qk_prep_fwd",
            functools.partial(_fwd_kernel, d=d, eps=eps, norm=norm),
            (yq, yk, yv, *gains, table), grid,
            flat + [_gain_spec(d)] * len(gains) + [tab], heads,
            [jax.ShapeDtypeStruct((B, w // d, T, d), y.dtype)
             for w, y in zip(widths, (yq, yk, yv))],
            bt * (2 * sum(widths) * yq.dtype.itemsize + 4 * d), interpret)


@functools.partial(jax.jit, static_argnames=("eps", "bt", "interpret"))
def _bwd(yq, yk, gq, gk, table, dq, dk, dv, *, eps, bt, interpret):
    """-> ``dyq``, ``dyk``, ``dyv`` [B, T, heads d] and the gains'
    gradients in their dtype (None without a norm, where ``yq`` and
    ``yk`` are None too: the rotation's transpose reads no input)."""
    with scope("hvd.attn.rope"):
        B, T, d = table.shape
        widths = [g.shape[1] * d for g in (dq, dk, dv)]
        grid, flat, heads, tab = _specs(B, T, widths, d, bt)
        norm = gq is not None
        first = [yq, yk, gq[None], gk[None]] if norm else []
        out = _call(
            "hvd_qk_prep_bwd",
            functools.partial(_bwd_kernel, d=d, eps=eps, norm=norm),
            (*first, dq, dk, dv, table), grid,
            (flat[:2] + [_gain_spec(d)] * 2 if norm else []) + heads + [tab],
            flat + [pl.BlockSpec((None, None, _SUBLANES, d),
                                 lambda b, t: (b, t, 0, 0))] * (2 * norm),
            [jax.ShapeDtypeStruct((B, T, w), g.dtype)
             for w, g in zip(widths, (dq, dk, dv))]
            + [jax.ShapeDtypeStruct((B, T // bt, _SUBLANES, d), F32)] * (2 * norm),
            bt * ((2 * sum(widths) + norm * sum(widths[:2]))
                  * dq.dtype.itemsize + 4 * d), interpret)
        if not norm:
            return (*out, None, None)
        return (*out[:3], *(dg.sum((0, 1, 2)).astype(g.dtype)
                            for dg, g in zip(out[3:], (gq, gk))))


def _step(T, tokens, interpret):
    """Whole packed tiles of tokens a step, ``tokens`` at most
    (``on_kernels`` saw to it that the sequence is made of them)."""
    return {"bt": _PACKED * _pick_block(T // _PACKED,
                                        max(tokens // _PACKED, 1)),
            "interpret": interpret}


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kernel(yq, yk, yv, gq, gk, table, eps):
    return _fwd(yq, yk, yv, gq, gk, table, eps=eps,
                **_step(yq.shape[1], TOKENS_A_STEP, _INTERPRET))


def _kernel_fwd(yq, yk, yv, gq, gk, table, eps):
    # What the backward reads and nothing else: without a norm the
    # rotation's transpose needs no input at all.
    keep = (yq, yk) if gq is not None else (None, None)
    return _kernel(yq, yk, yv, gq, gk, table, eps), (*keep, gq, gk, table)


def _kernel_bwd(eps, res, grads):
    yq, yk, gq, gk, table = res
    dyq, dyk, dyv, dgq, dgk = _bwd(
        yq, yk, gq, gk, table, *grads, eps=eps,
        **_step(table.shape[1], TOKENS_A_STEP, _INTERPRET))
    # The table is made of positions: nothing reads its cotangent.
    return dyq, dyk, dyv, dgq, dgk, jnp.zeros_like(table)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def qk_prep(yq, yk, yv, q_gain, k_gain, positions, theta, head_dim, eps,
            mesh=None):
    """``yq`` [B, T, H d], ``yk``, ``yv`` [B, T, Hkv d] in the compute
    dtype as the projections leave them -> ``q`` [B, H, T, d], ``k``,
    ``v`` [B, Hkv, T, d]: q and k normed a head (``q_gain``, ``k_gain``
    [d] in the compute dtype; both None: no norm) and turned by
    ``positions`` [B, T] (``theta`` None: not turned). Differentiable in
    the three and the gains.

    ``mesh``: as ``flash_attention``'s. GSPMD cannot partition a Mosaic
    call, so on a mesh of several devices the kernels run under
    ``jax.shard_map``, batch over ``data`` / ``fsdp`` and heads over
    ``tensor``, each device on its own shard."""
    from jax.sharding import PartitionSpec as P

    table = rotation_table(positions, theta, head_dim)

    def run(*operands):
        return _kernel(*operands, float(eps))

    if mesh is None or mesh.size == 1:
        return run(yq, yk, yv, q_gain, k_gain, table)
    batch, heads = _kernel_mesh_specs(mesh, yq.shape[0],
                                      yq.shape[2] // head_dim,
                                      yk.shape[2] // head_dim)
    flat, major = P(batch, None, heads), P(batch, heads, None, None)
    gain = None if q_gain is None else P(None)
    return jax.shard_map(
        run, mesh=mesh, in_specs=(flat, flat, flat, gain, gain,
                                  P(batch, None, None)),
        out_specs=(major, major, major), check_vma=False,
    )(yq, yk, yv, q_gain, k_gain, table)
