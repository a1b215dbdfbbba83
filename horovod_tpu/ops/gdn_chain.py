"""The elementwise chain round the gated delta rule of a Gated DeltaNet
mixer (``models/llama.py:_gated_delta_net``), each of its two stages in
ONE pass over HBM forward and one backward.

Stage one, before the rule (:func:`chain_in`): from ``qkvz`` [B, T,
(2 hk + 2 hv) d] as the projection leaves it, ``[q, k, v, z]`` side by
side, and the taps [taps, (2 hk + hv) d]: the depthwise causal
convolution summed in float32, the cast to the compute dtype, SiLU, and
for ``q`` and ``k`` the unit vector a key head in float32 (``q`` times
``d^-1/2``) -> ``q``, ``k`` [B, T, hk d], ``v`` [B, T, hv d], and ``z``
[B, T, hv d] as it stands. Stage two, behind the rule
(:func:`chain_out`): ``RMSNorm(o) * gain * SiLU(z)`` a value head of
``o``, ``z`` [B, T, hv d], statistics in float32, rounded where the
expression rounds.

A kernel pair a stage behind a ``custom_vjp`` that saves its INPUTS and
nothing else: the backward kernels run the convolution, SiLU and the
norms again in VMEM, so no float32 ``[B, T, 8192]`` and no second copy
of anything crosses HBM for them.

How the blocks lie: as the projections' matmuls leave and take their
operands, ``[B, T, heads * d]`` with the TOKENS on the sublanes. A block
is a tile of ``bt`` tokens by ``hb`` whole heads' lanes. (Read as ``[B,
T, heads, d]`` the same bytes are tiled with the HEADS on the sublanes:
a reshape between the two is a copy of the whole array on a TPU, and
the parent's program paid for several, in float32.) Inside a grid step a
loop walks the tile ``TOKENS_A_PASS`` tokens at a time; a tap that reads
``back`` tokens ago is a rotation down the sublanes of the pass's window
(its own tokens and the ``halo`` before them); a head's norm is one lane
reduction a row.

- ``hvd_gdn_chain_in_fwd``: grid ``(B, hk / hb, T / bt)``, every axis
  parallel; a step takes ``hb`` key heads of ``q``, of ``k``, and the
  ``hv / hk * hb`` value heads they serve of ``v`` (windows on the one
  ``qkvz``, no split copy), and beside each tile the ``halo`` tokens
  before it (zeros before token 0).
- ``hvd_gdn_chain_in_bwd``: grid ``(B, (2 hk + 2 hv) / hb, T / bt)``
  over ``qkvz``'s columns as they lie, so that ONE output holds the
  whole of ``d qkvz`` (``dz`` copied into its columns: no concatenate
  behind the kernel); the token axis is sequential and its index maps
  count DOWN: the convolution's transpose needs the ``taps - 1`` tokens
  AFTER a tile, which the step before left in a VMEM scratch
  (``hvd_gdn_rule_bwd`` carries its state the same way). A cotangent
  that is not a column block's own is parked on block 0 and not fetched
  again. The taps' gradient accumulates over a sequence in float32, a
  sublane tile of partial sums a tap, and is summed outside.
- ``hvd_gdn_chain_out_fwd`` / ``_bwd``: grid ``(B, hv / hb, T / bt)``;
  the backward accumulates the gain's gradient over the (then
  sequential) token axis, summed over batch and heads outside.

Keys and values of TWO widths, neither a lane tile (30 heads of 96 and
of 192: three quarters and one and a half tiles; :func:`on_kernels` says
where): the same four calls under the same names, on ONE STRIP. ``q``
ends at column 2880 = 22.5 lane tiles, so ``k`` starts in the middle of
one and neither ``q``'s columns nor ``k``'s fall into blocks of whole
tiles; ``[q | k]`` TOGETHER do (60 key heads = 45 tiles), four key heads
or two value heads to a LANE GROUP of three tiles (:func:`_group`). So
stage one walks ``qkvz``'s convolved columns as they lie, ``[q | k]`` as
one run of key heads and ``v`` behind it, in column blocks of whole lane
groups (``LANES_A_STEP``: 1152 lanes, 12 key or 6 value heads), and
writes ONE strip ``[q | k | v]``: grid ``(B, columns / width, T / bt)``
forward, the same plus ``z``'s blocks backward, one cotangent strip
``[dq | dk | dv]`` in. Where ``q`` ends inside a block (column 2880 is
the middle of block 2) a lane knows its scale by its column
(:func:`_strip_scale`). A head's norm is a reduction over lanes that
start and end inside a tile: :func:`_head_sums` sums each head of a lane
group under its mask and lays the sum back under it, so no value is cut
or shifted off a tile's edge. The caller cuts the strip into ``q``,
``k``, ``v`` for the rule, which reads them as they lie, ``[B, T, H
d]`` (``ops/gated_delta_rule.py``, all 30 heads a grid step), and lays
the three cotangents side by side behind it. (XLA makes copies of the
cuts: of the scope's 45.2 ms a step in the cell, the four kernels are
30.8, the cuts 7.3, the gates 3.6, the ``concatenate`` 3.5; the
reshapes between ``[B, T, H d]`` and ``[B, T, H, d]``, a relayout where
``d`` is no lane tile, were 11.9 more until the rule took the strip's
own layout: PERF.md sections 5 and 7, PR 62; what is left is what a
rule that reads the strip by an index map would remove.) Why not the
other two ways:
columns padded to whole tiles a head (96 -> 128, 192 -> 256) cost a
third more bytes through HBM in every pass and a repacking copy besides,
for kernels that wait for HBM already; a layout with the heads on the
sublanes is the reshape of the whole array that this module exists to
avoid. Stage two's steps take six value heads (nine tiles).

The names are the calls' ``kernel_metadata``, what a device trace shows.
Each kernel sits behind ONE jitted function, so a program pays one
Mosaic lowering a kernel whatever the number of layers and phases
(``ops/gated_delta_rule.py`` says why). :func:`on_kernels` reads the
carrier off the operands (``ops/_platform.py``); elsewhere the caller's
``jnp`` expression runs, which is also the tests' reference (the
kernels run there in interpret mode under ``_INTERPRET``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import _pick_block
from horovod_tpu.utils.spans import scope

F32 = jnp.float32
# Tests flip this to run the kernels in pallas interpret mode on the CPU
# (as ``flash_attention._INTERPRET``).
_INTERPRET = False
# What one grid step takes: so many tokens by so many heads (or the
# largest divisors under them), walked so many tokens a pass.
TOKENS_A_STEP = 256
HEADS_A_STEP = 8
TOKENS_A_PASS = 32
UNIT_EPS = 1e-6      # under the root of a key head's squared length
# Keys and values of TWO widths: the lanes one grid step takes of the
# strip (or the largest whole number of lane groups under it).
LANES_A_STEP = 1152
_PACKED = 16         # rows of a packed bfloat16 tile, and of a halo
_SUBLANES = 8        # rows of a float32 tile, and of a partial sum
_LANES = 128         # lanes of a tile


def _group(d):
    """The fewest lanes that are whole heads of ``d`` AND whole lane
    tiles (96 -> 384: four heads on three tiles; 128 -> 128)."""
    return d * _LANES // math.gcd(d, _LANES)


def _strip_width(key_heads, value_heads, key_dim, value_dim):
    """The lanes a grid step takes where keys and values differ in
    width, or None where ``qkvz`` has no such step: a whole number of
    both widths' lane groups that divides ``[q | k]`` and ``v`` (so
    ``v``, ``z`` and the taps' columns start on a block's edge), the
    largest under ``LANES_A_STEP`` or else ONE such."""
    g = math.lcm(_group(key_dim), _group(value_dim))
    qk, v = 2 * key_heads * key_dim, value_heads * value_dim
    if not key_heads or not value_heads or qk % g or v % g:
        return None
    n = math.gcd(qk, v) // g
    return g * max(m for m in range(1, n + 1)
                   if n % m == 0 and (m == 1 or m * g <= LANES_A_STEP))


def on_kernels(x, key_dim, value_dim, key_heads=0, value_heads=0):
    """True where the chain runs as the kernel pairs: the mixer's input
    ``x`` on a TPU (or ``_INTERPRET``, the tests' switch) and either
    keys and values of one width (``qkvz`` is then whole heads side by
    side) or, given the head counts, of two widths whose columns fall
    into steps of whole lane groups (:func:`_strip_width`)."""
    return (key_dim == value_dim or _strip_width(
        key_heads, value_heads, key_dim, value_dim) is not None) \
        and use_pallas("gdn_chain", (x,), _INTERPRET)


def _heads_a_step(heads, d):
    """Heads a step of ``[.., heads * d]``: the largest divisor under
    ``HEADS_A_STEP`` whose lanes are whole tiles, or (no such divisor:
    the tests' narrow heads) the largest."""
    whole = [h for h in range(1, min(heads, HEADS_A_STEP) + 1)
             if heads % h == 0 and h * d % _LANES == 0]
    return max(whole) if whole else _pick_block(heads, HEADS_A_STEP)


def _tiling(T, heads, d):
    """(tokens a step, tokens a pass, heads a step), and how it runs."""
    bt = _pick_block(T, TOKENS_A_STEP)
    return {"bt": bt, "sub": _pick_block(bt, TOKENS_A_PASS),
            "hb": _heads_a_step(heads, d), "interpret": _INTERPRET}


def _halo(bt, taps):
    """The tokens read before a tile: they hold the ``taps - 1`` a tap
    reaches back and divide the tile (a block index counts in its own
    size): a whole packed tile of rows where the tile has them, else
    the least such."""
    if bt % _PACKED == 0 and taps <= _PACKED:
        return _PACKED
    for h in range(max(taps - 1, 1), bt + 1):
        if bt % h == 0:
            return h
    raise ValueError(f"{taps} taps reach further back than a tile of "
                     f"{bt} tokens")


def _kept_rows(sub):
    """Rows of a pass's partial sums (:func:`_partial_sums`)."""
    return _SUBLANES if sub % _SUBLANES == 0 else 1


def _call(name, kernel, operands, grid, in_specs, out_specs, out_shape,
          scratch, sequential, interpret):
    """``metadata`` is the name a device trace shows of the call
    (``ops/flash_attention.py:_pallas_dispatch``). Grid ``(batch, heads,
    tokens)``; the token axis in order where a step hands the next one
    something (``sequential``)."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel",
                "arbitrary" if sequential else "parallel")),
    )(*operands)


def _rows(i, sub, extra=0):
    """Pass ``i``'s rows of a tile (and ``extra`` more), aligned where
    the pass is whole sublane tiles."""
    start = i * sub
    return pl.ds(pl.multiple_of(start, _SUBLANES)
                 if sub % _SUBLANES == 0 else start, sub + extra)


def _sigmoid(x):
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _silu_grad(x, s):
    """d(x sigmoid(x)) / dx with ``s`` = sigmoid(x)."""
    return s * (1.0 + x * (1.0 - s))


def _head_sums(x, d):
    """[rows, heads * d] -> the same shape: every lane holds the sum
    over its head's ``d`` lanes. Heads of whole lane tiles: a slice, a
    lane reduction and a broadcast a head. Heads that start and end
    inside a tile (96, 192): a lane group (:func:`_group`) at a time,
    every head of it summed under its mask and laid back under it; no
    value is cut or moved off a tile's edge."""
    rows, width = x.shape
    g = _group(d)
    if g == d or width % g:
        return jnp.concatenate(
            [jnp.broadcast_to(jnp.sum(x[:, j:j + d], -1, keepdims=True),
                              (rows, d))
             for j in range(0, width, d)], axis=-1)
    lane = lax.broadcasted_iota(jnp.int32, (rows, g), 1)
    heads = [jnp.logical_and(lane >= j, lane < j + d)
             for j in range(0, g, d)]
    groups = []
    for at in range(0, width, g):
        part, sums = x[:, at:at + g], None
        for mine in heads:
            total = jnp.sum(jnp.where(mine, part, 0.0), -1, keepdims=True)
            sums = jnp.broadcast_to(total, (rows, g)) if sums is None \
                else jnp.where(mine, total, sums)
        groups.append(sums)
    return groups[0] if len(groups) == 1 else jnp.concatenate(groups, -1)


def _partial_sums(x):
    """[rows, W] -> [a sublane tile of rows (or one), W] that sum to the
    column sums: plain vector adds, the last eight rows left to the
    caller."""
    rows, width = x.shape
    keep = _kept_rows(rows)
    return x.reshape(rows // keep, keep, width).sum(0)


# ---------------------------------------------------------------------
# Stage one: taps, SiLU, unit vectors.
# ---------------------------------------------------------------------

def _fill(ext_ref, x_ref, halo_ref, first):
    """``ext_ref`` [halo + bt, W] float32 <- the halo (zeros where the
    tile is the sequence's ``first``) and the tile behind it."""
    h = halo_ref.shape[0]

    @pl.when(first)
    def _zeros():
        ext_ref[:h] = jnp.zeros((h, ext_ref.shape[1]), F32)

    @pl.when(jnp.logical_not(first))
    def _halo():
        ext_ref[:h] = halo_ref[...].astype(F32)

    ext_ref[h:] = x_ref[...].astype(F32)


def _taps_sum(ext_ref, w, i, sub, h):
    """Pass ``i`` of a tile: (its tokens as they were 0, 1, ... ``taps -
    1`` tokens ago, their convolution in float32). ``_causal_taps``'
    sum, term by term in its order."""
    taps = w.shape[0]
    window = ext_ref[_rows(i, sub, h)]           # the halo, then the pass
    ago = [window[h:]] + [pltpu.roll(window, back, 0)[h:]
                          for back in range(1, taps)]
    conv = ago[0] * w[taps - 1:]
    for back in range(1, taps):
        conv = conv + ago[back] * w[taps - 1 - back:taps - back]
    return ago, conv


def _conv_silu(ext_ref, w, i, sub, h, dt):
    """:func:`_taps_sum` with the convolution rounded to the compute
    dtype and read back, and its sigmoid."""
    ago, conv = _taps_sum(ext_ref, w, i, sub, h)
    c = conv.astype(dt).astype(F32)
    return ago, c, _sigmoid(c)


def _taps_transpose(dconv, after, ago, w, dx_ref, dw_ref, i, sub):
    """The convolution's transpose over pass ``i``: from its cotangent
    ``dconv`` [sub, W] float32 and that of the ``halo`` tokens ``after``
    the pass, the input's cotangent into ``dx_ref`` (summed once, in
    float32) and the taps' onto ``dw_ref``; -> the cotangent at the
    pass's first ``halo`` tokens, which the pass before it reads."""
    taps, h = w.shape[0], after.shape[0]
    ext = jnp.concatenate([dconv, after], axis=0)
    dx = dconv * w[taps - 1:]
    for back in range(1, taps):
        dx = dx + pltpu.roll(ext, sub + h - back, 0)[:sub] \
            * w[taps - 1 - back:taps - back]
    dx_ref[_rows(i, sub)] = dx.astype(dx_ref.dtype)
    for back in range(taps):     # tap k met the token so far back
        dw_ref[taps - 1 - back] += _partial_sums(dconv * ago[back])
    return ext[:h]


def _times(y, unit):
    """``y`` times a head's scale: a number (1.0: as it stands) or a
    row of one a lane."""
    return y if isinstance(unit, float) and unit == 1.0 else y * unit


def _strip_scale(width, key_width, scale):
    """The scale of each lane of column block ``program_id(1)`` of the
    strip ``[q | k]``, [1, width] float32: ``q``'s columns, the first
    ``key_width``, carry ``scale``; ``k``'s carry 1."""
    lane = pl.program_id(1) * width \
        + lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return jnp.where(lane < key_width, scale, 1.0).astype(F32)


def _fwd_section(ext_ref, x_ref, halo_ref, w_ref, out_ref, at, first,
                 unit, d, sub):
    """A tile of ``qkvz``'s convolved columns (the sequence's ``first``
    or not) -> ``out_ref``'s lanes from ``at``: taps, SiLU and, with
    ``unit`` (:func:`_times`), the unit vector a head of ``d`` times
    it."""
    bt, width = x_ref.shape
    h, dt = halo_ref.shape[0], out_ref.dtype
    _fill(ext_ref, x_ref, halo_ref, first)
    w = w_ref[...].astype(F32)

    def one_pass(i, carry):
        _, c, s = _conv_silu(ext_ref, w, i, sub, h, dt)
        y = (c * s).astype(dt)
        if unit is not None:
            y = y.astype(F32)
            y = y * lax.rsqrt(_head_sums(y * y, d) + UNIT_EPS)
            y = _times(y, unit).astype(dt)
        out_ref[_rows(i, sub), at:at + width] = y
        return carry

    lax.fori_loop(0, bt // sub, one_pass, 0)


def _in_fwd_kernel(*refs, n, d, scale, sub):
    """``refs``: ``n`` tiles of ``qkvz`` (``q``'s heads, ``k``'s, then
    ``v``'s in ``n - 2`` pieces), their halos, their taps; ``q``, ``k``,
    ``v`` out; the scratch."""
    xs, halos, ws = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    q_ref, k_ref, v_ref, ext_ref = refs[3 * n:]
    first = pl.program_id(2) == 0
    width = q_ref.shape[1]

    def section(j, out_ref, at, unit):
        _fwd_section(ext_ref, xs[j], halos[j], ws[j], out_ref, at, first,
                     unit, d, sub)

    section(0, q_ref, 0, scale)
    section(1, k_ref, 0, 1.0)
    for j in range(n - 2):
        section(2 + j, v_ref, j * width, None)


def _strip_fwd_kernel(x_ref, halo_ref, w_ref, y_ref, ext_ref, *, nqk,
                      key_width, d, scale, sub):
    """Keys and values of two widths: column block ``c`` of ``qkvz``'s
    convolved columns as they lie, ``[q | k]`` one strip of key heads
    (blocks under ``nqk``; where ``q`` ends inside a block a lane knows
    its scale by its column), then ``v``'s."""
    c, first = pl.program_id(1), pl.program_id(2) == 0
    lanes = _strip_scale(y_ref.shape[1], key_width, scale)

    def section(unit):
        _fwd_section(ext_ref, x_ref, halo_ref, w_ref, y_ref, 0, first,
                     unit, d, sub)

    pl.when(c < nqk)(lambda: section(lanes))
    pl.when(c >= nqk)(lambda: section(None))


@functools.partial(jax.jit, static_argnames=(
    "hk", "hv", "bt", "halo", "sub", "hb", "interpret"))
def _in_fwd(qkvz, taps, *, hk, hv, bt, halo, sub, hb, interpret):
    """-> ``q``, ``k`` [B, T, hk d], ``v``, ``z`` [B, T, hv d]. Jitted
    on its own, and the scope again, as ``gated_delta_rule._kernel_fwd``
    has it and says why."""
    with scope("hvd.gdn.chain"):
        B, T, total = qkvz.shape
        d = total // (2 * hk + 2 * hv)
        r, nq, width = hv // hk, hk // hb, hb * d
        # the head blocks a step takes, in blocks of hb heads: q's, k's,
        # and the r blocks of v that those key heads serve
        at = [lambda c: c, lambda c: nq + c] + [
            lambda c, j=j: 2 * nq + r * c + j for j in range(r)]

        def spec(tokens, token_block):
            return [pl.BlockSpec(
                (None, tokens, width),
                lambda b, c, t, a=a: (b, token_block(t), a(c)))
                for a in at]

        tiles = spec(bt, lambda t: t)
        halos = spec(halo, lambda t: jnp.maximum(t * (bt // halo) - 1, 0))
        tap = [pl.BlockSpec((taps.shape[0], width),
                            lambda b, c, t, a=a: (0, a(c))) for a in at]
        n = len(at)

        def out(lanes):
            return pl.BlockSpec((None, bt, lanes), lambda b, c, t: (b, t, c))

        q, k, v = _call(
            "hvd_gdn_chain_in_fwd",
            functools.partial(_in_fwd_kernel, n=n, d=d, scale=d ** -0.5,
                              sub=sub),
            [qkvz] * (2 * n) + [taps] * n, (B, nq, T // bt),
            tiles + halos + tap, [out(width), out(width), out(r * width)],
            [jax.ShapeDtypeStruct((B, T, heads * d), qkvz.dtype)
             for heads in (hk, hk, hv)],
            [pltpu.VMEM((halo + bt, width), F32)], False, interpret)
        return q, k, v, qkvz[:, :, (2 * hk + hv) * d:]


def _column_blocks(width, bt, last, first, end, tokens=None,
                   token_block=None):
    """A backward call's view of an operand that has only the column
    blocks [first, end) of ``d qkvz``'s: ``tokens`` (a tile's ``bt``)
    by ``width``, the token tiles counted down from ``last``
    (``token_block`` maps the grid step otherwise); any other column
    block parks on block 0 (fetched once, as long as the index
    stands)."""
    token_block = token_block or (lambda t: last - t)

    def index(b, c, t):
        mine = jnp.logical_and(c >= first, c < end)
        return tuple(jnp.where(mine, i, 0) for i in (
            b, token_block(t), c - first))
    return pl.BlockSpec((None, tokens or bt, width), index)


def _bwd_section(x_ref, halo_ref, w_ref, d_ref, dx_ref, dw_ref, ext_ref,
                 next_ref, t, first, unit, d, sub):
    """A tile of ``d qkvz``'s convolved columns from the cotangent in
    ``d_ref``, the token tiles walked from the last (``_in_bwd_kernel``
    says how: ``t`` is the grid step, ``first`` whether its tile is the
    sequence's first); ``unit`` and ``d`` as :func:`_fwd_section`'s."""
    bt, _ = dx_ref.shape
    h, dt = halo_ref.shape[0], dx_ref.dtype
    _fill(ext_ref, x_ref, halo_ref, first)

    @pl.when(t == 0)
    def _start():
        next_ref[...] = jnp.zeros_like(next_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...].astype(F32)
    passes = bt // sub

    def one_pass(j, after):
        i = passes - 1 - j
        ago, c_, s = _conv_silu(ext_ref, w, i, sub, h, dt)
        dy = d_ref[_rows(i, sub)].astype(F32)
        if unit is not None:
            # through the unit vector: y -> y * rsqrt(|y|^2 + eps)
            y = (c_ * s).astype(dt).astype(F32)
            rs = lax.rsqrt(_head_sums(y * y, d) + UNIT_EPS)
            u = y * rs
            dy = _times(dy, unit)
            dy = rs * (dy - u * _head_sums(dy * u, d))
            dy = dy.astype(dt).astype(F32)
        # SiLU, then the cast the convolution's sum went through
        dconv = (dy * _silu_grad(c_, s)).astype(dt).astype(F32)
        return _taps_transpose(dconv, after, ago, w, dx_ref, dw_ref, i,
                               sub)

    next_ref[...] = lax.fori_loop(0, passes, one_pass, next_ref[...])


def _in_bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dz_ref,
                   dx_ref, dw_ref, ext_ref, next_ref, *, nq, nx, d, scale,
                   sub):
    """A column block of ``d qkvz``: grid step ``t`` holds token tile
    ``T / bt - 1 - t`` (the index maps count down). ``next_ref``
    [halo, W] float32 is the convolution's cotangent at the first
    tokens of the tile AFTER this one, which the step before left; the
    taps' gradient accumulates in ``dw_ref`` over the sequence."""
    c, t = pl.program_id(1), pl.program_id(2)
    first = t == pl.num_programs(2) - 1

    def section(d_ref, unit):
        _bwd_section(x_ref, halo_ref, w_ref, d_ref, dx_ref, dw_ref, ext_ref,
                     next_ref, t, first, unit, d, sub)

    pl.when(c < nq)(lambda: section(dq_ref, scale))
    pl.when(jnp.logical_and(c >= nq, c < 2 * nq))(
        lambda: section(dk_ref, 1.0))
    pl.when(jnp.logical_and(c >= 2 * nq, c < nx))(
        lambda: section(dv_ref, None))

    @pl.when(c >= nx)
    def _z():
        dx_ref[...] = dz_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "hk", "hv", "bt", "halo", "sub", "hb", "interpret"))
def _in_bwd(qkvz, taps, dq, dk, dv, dz, *, hk, hv, bt, halo, sub, hb,
            interpret):
    """-> ``d qkvz`` whole, and the taps' gradient in their dtype."""
    with scope("hvd.gdn.chain"):
        B, T, total = qkvz.shape
        d, ntaps = total // (2 * hk + 2 * hv), taps.shape[0]
        nq, nv, width = hk // hb, hv // hb, hb * d
        nx, last = 2 * nq + nv, T // bt - 1
        keep = _kept_rows(sub)

        tile = functools.partial(_column_blocks, width, bt, last)

        dx, dw = _call(
            "hvd_gdn_chain_in_bwd",
            functools.partial(_in_bwd_kernel, nq=nq, nx=nx, d=d,
                              scale=d ** -0.5, sub=sub),
            (qkvz, qkvz, taps, dq, dk, dv, dz), (B, nx + nv, T // bt),
            [tile(0, nx),
             tile(0, nx, halo, lambda t: jnp.maximum(
                 (last - t) * (bt // halo) - 1, 0)),
             pl.BlockSpec((ntaps, width),
                          lambda b, c, t: (0, jnp.minimum(c, nx - 1))),
             tile(0, nq), tile(nq, 2 * nq), tile(2 * nq, nx),
             tile(nx, nx + nv)],
            [pl.BlockSpec((None, bt, width),
                          lambda b, c, t: (b, last - t, c)),
             pl.BlockSpec((None, ntaps, keep, width), lambda b, c, t: (
                 b, 0, 0, jnp.minimum(c, nx - 1)))],
            [jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
             jax.ShapeDtypeStruct((B, ntaps, keep, taps.shape[1]), F32)],
            [pltpu.VMEM((halo + bt, width), F32),
             pltpu.VMEM((halo, width), F32)], True, interpret)
        return dx, dw.sum((0, 2)).astype(taps.dtype)


def _in_step(qkvz, taps, hk, hv):
    step = _tiling(qkvz.shape[1], hk, qkvz.shape[2] // (2 * hk + 2 * hv))
    return {"halo": _halo(step["bt"], taps.shape[0]), **step}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _kernel_in(qkvz, taps, hk, hv):
    return _in_fwd(qkvz, taps, hk=hk, hv=hv,
                   **_in_step(qkvz, taps, hk, hv))


def _kernel_in_fwd(qkvz, taps, hk, hv):
    return _kernel_in(qkvz, taps, hk, hv), (qkvz, taps)


def _kernel_in_bwd(hk, hv, res, grads):
    qkvz, taps = res
    return _in_bwd(qkvz, taps, *grads, hk=hk, hv=hv,
                   **_in_step(qkvz, taps, hk, hv))


_kernel_in.defvjp(_kernel_in_fwd, _kernel_in_bwd)


# Keys and values of two widths: the strip (the module's docstring).

@functools.partial(jax.jit, static_argnames=(
    "kw", "vw", "dk", "width", "bt", "halo", "sub", "interpret"))
def _strip_fwd(qkvz, taps, *, kw, vw, dk, width, bt, halo, sub, interpret):
    """-> the strip ``[q | k | v]`` [B, T, 2 kw + vw]."""
    with scope("hvd.gdn.chain"):
        B, T, _ = qkvz.shape
        nx = (2 * kw + vw) // width

        def rows(tokens, token_block):
            return pl.BlockSpec((None, tokens, width),
                                lambda b, c, t: (b, token_block(t), c))

        tile = rows(bt, lambda t: t)
        return _call(
            "hvd_gdn_chain_in_fwd",
            functools.partial(_strip_fwd_kernel, nqk=2 * kw // width,
                              key_width=kw, d=dk, scale=dk ** -0.5,
                              sub=sub),
            (qkvz, qkvz, taps), (B, nx, T // bt),
            [tile,
             rows(halo, lambda t: jnp.maximum(t * (bt // halo) - 1, 0)),
             pl.BlockSpec((taps.shape[0], width), lambda b, c, t: (0, c))],
            tile, jax.ShapeDtypeStruct((B, T, nx * width), qkvz.dtype),
            [pltpu.VMEM((halo + bt, width), F32)], False, interpret)


def _strip_bwd_kernel(x_ref, halo_ref, w_ref, dy_ref, dz_ref, dx_ref,
                      dw_ref, ext_ref, next_ref, *, nqk, nx, key_width, d,
                      scale, sub):
    """``_in_bwd_kernel`` over the strip: one cotangent ``[dq | dk |
    dv]`` whose blocks lie as ``qkvz``'s do."""
    c, t = pl.program_id(1), pl.program_id(2)
    first = t == pl.num_programs(2) - 1
    lanes = _strip_scale(dx_ref.shape[1], key_width, scale)

    def section(unit):
        _bwd_section(x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref,
                     ext_ref, next_ref, t, first, unit, d, sub)

    pl.when(c < nqk)(lambda: section(lanes))
    pl.when(jnp.logical_and(c >= nqk, c < nx))(lambda: section(None))

    @pl.when(c >= nx)
    def _z():
        dx_ref[...] = dz_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "kw", "vw", "dk", "width", "bt", "halo", "sub", "interpret"))
def _strip_bwd(qkvz, taps, dy, dz, *, kw, vw, dk, width, bt, halo, sub,
               interpret):
    """-> ``d qkvz`` whole, and the taps' gradient in their dtype."""
    with scope("hvd.gdn.chain"):
        B, T, _ = qkvz.shape
        ntaps = taps.shape[0]
        nx, nz, last = (2 * kw + vw) // width, vw // width, T // bt - 1
        keep = _kept_rows(sub)

        tile = functools.partial(_column_blocks, width, bt, last)

        dx, dw = _call(
            "hvd_gdn_chain_in_bwd",
            functools.partial(_strip_bwd_kernel, nqk=2 * kw // width,
                              nx=nx, key_width=kw, d=dk, scale=dk ** -0.5,
                              sub=sub),
            (qkvz, qkvz, taps, dy, dz), (B, nx + nz, T // bt),
            [tile(0, nx),
             tile(0, nx, halo, lambda t: jnp.maximum(
                 (last - t) * (bt // halo) - 1, 0)),
             pl.BlockSpec((ntaps, width),
                          lambda b, c, t: (0, jnp.minimum(c, nx - 1))),
             tile(0, nx), tile(nx, nx + nz)],
            [pl.BlockSpec((None, bt, width),
                          lambda b, c, t: (b, last - t, c)),
             pl.BlockSpec((None, ntaps, keep, width), lambda b, c, t: (
                 b, 0, 0, jnp.minimum(c, nx - 1)))],
            [jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
             jax.ShapeDtypeStruct((B, ntaps, keep, taps.shape[1]), F32)],
            [pltpu.VMEM((halo + bt, width), F32),
             pltpu.VMEM((halo, width), F32)], True, interpret)
        return dx, dw.sum((0, 2)).astype(taps.dtype)


def _strip_step(qkvz, taps, hk, hv, dk, dv):
    bt = _pick_block(qkvz.shape[1], TOKENS_A_STEP)
    return {"kw": hk * dk, "vw": hv * dv, "dk": dk,
            "width": _strip_width(hk, hv, dk, dv), "bt": bt,
            "halo": _halo(bt, taps.shape[0]),
            "sub": _pick_block(bt, TOKENS_A_PASS), "interpret": _INTERPRET}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _kernel_strip(qkvz, taps, hk, hv, dk, dv):
    kw, vw = hk * dk, hv * dv
    y = _strip_fwd(qkvz, taps, **_strip_step(qkvz, taps, hk, hv, dk, dv))
    return (y[:, :, :kw], y[:, :, kw:2 * kw], y[:, :, 2 * kw:],
            qkvz[:, :, 2 * kw + vw:])


def _kernel_strip_fwd(qkvz, taps, hk, hv, dk, dv):
    return _kernel_strip(qkvz, taps, hk, hv, dk, dv), (qkvz, taps)


def _kernel_strip_bwd(hk, hv, dk, dv, res, grads):
    qkvz, taps = res
    return _strip_bwd(qkvz, taps, jnp.concatenate(grads[:3], axis=-1),
                      grads[3], **_strip_step(qkvz, taps, hk, hv, dk, dv))


_kernel_strip.defvjp(_kernel_strip_fwd, _kernel_strip_bwd)


def chain_in(qkvz, taps, key_heads, value_heads, key_dim=0, value_dim=0):
    """Stage one on the kernels: ``qkvz`` [B, T, 2 hk dk + 2 hv dv] in
    the compute dtype and the taps [taps, 2 hk dk + hv dv] -> ``q``,
    ``k`` [B, T, hk dk] (unit vectors a head, ``q`` times ``dk^-1/2``),
    ``v`` and ``z`` [B, T, hv dv]. Without the widths: one ``d`` for
    keys and values, ``hk`` dividing ``hv``. With two that differ: the
    strip (:func:`on_kernels` says where it has a step).
    Differentiable in both operands."""
    if value_heads % key_heads:
        raise ValueError(f"{value_heads} value heads are no multiple of "
                         f"{key_heads} key heads")
    if key_dim == value_dim:
        return _kernel_in(qkvz, taps, key_heads, value_heads)
    if _strip_width(key_heads, value_heads, key_dim, value_dim) is None:
        raise ValueError(
            f"{key_heads} key heads of {key_dim} and {value_heads} value "
            f"heads of {value_dim}: [q | k] and v do not fall into blocks "
            "of whole lane groups of both widths")
    return _kernel_strip(qkvz, taps, key_heads, value_heads, key_dim,
                         value_dim)


# ---------------------------------------------------------------------
# Stage two: the gated norm behind the rule.
# ---------------------------------------------------------------------

def _gated_norm(o_ref, z_ref, gain, at, d, eps):
    """A pass's rows of ``RMSNorm(o) * gain * SiLU(z)``, every value the
    backward reads again: ``o / rms`` in float32 and its ``1 / rms``,
    that product rounded, times the gain rounded, ``z``, its sigmoid,
    SiLU rounded. All float32, the rounded ones at the compute dtype's
    values."""
    dt = o_ref.dtype
    o, z = o_ref[at].astype(F32), z_ref[at].astype(F32)
    rs = lax.rsqrt(_head_sums(o * o, d) * (1.0 / d) + eps)
    u = o * rs
    y1 = u.astype(dt).astype(F32)
    y2 = (y1 * gain).astype(dt).astype(F32)
    s = _sigmoid(z)
    return u, rs, y1, y2, z, s, (z * s).astype(dt).astype(F32)


def _out_fwd_kernel(o_ref, z_ref, g_ref, y_ref, *, d, eps, sub):
    gain = g_ref[...].astype(F32)

    def one_pass(i, carry):
        at = _rows(i, sub)
        *_, y2, _, _, sz = _gated_norm(o_ref, z_ref, gain, at, d, eps)
        y_ref[at] = (y2 * sz).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, o_ref.shape[0] // sub, one_pass, 0)


def _out_bwd_kernel(o_ref, z_ref, g_ref, dy_ref, do_ref, dz_ref, dg_ref, *,
                    d, eps, sub):
    dt = o_ref.dtype
    gain = g_ref[...].astype(F32)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    def one_pass(i, carry):
        at = _rows(i, sub)
        u, rs, y1, y2, z, s, sz = _gated_norm(o_ref, z_ref, gain, at, d,
                                              eps)
        dy = dy_ref[at].astype(F32)
        dsz = (dy * y2).astype(dt).astype(F32)
        dz_ref[at] = (dsz * _silu_grad(z, s)).astype(dt)
        dy2 = (dy * sz).astype(dt).astype(F32)
        du = (dy2 * gain).astype(dt).astype(F32)
        do_ref[at] = (rs * (du - u * _head_sums(du * u, d) * (1.0 / d))
                      ).astype(dt)
        dg_ref[...] += _partial_sums(dy2 * y1)
        return carry

    lax.fori_loop(0, o_ref.shape[0] // sub, one_pass, 0)


def _out_specs(o, gain, bt, hb):
    """(grid, a block of rows, the gain's block, the gain laid ``hb``
    times side by side: a step's lanes) for ``o`` [B, T, heads * d]."""
    B, T, total = o.shape
    width = hb * gain.shape[0]
    rows = pl.BlockSpec((None, bt, width), lambda b, c, t: (b, t, c))
    return (B, total // width, T // bt), rows, \
        pl.BlockSpec((1, width), lambda b, c, t: (0, 0)), \
        jnp.tile(gain, hb)[None]


@functools.partial(jax.jit, static_argnames=(
    "eps", "bt", "sub", "hb", "interpret"))
def _out_fwd(o, z, gain, *, eps, bt, sub, hb, interpret):
    with scope("hvd.gdn.chain"):
        grid, rows, g, lanes = _out_specs(o, gain, bt, hb)
        return _call(
            "hvd_gdn_chain_out_fwd",
            functools.partial(_out_fwd_kernel, d=gain.shape[0], eps=eps,
                              sub=sub),
            (o, z, lanes), grid, [rows, rows, g], rows,
            jax.ShapeDtypeStruct(o.shape, o.dtype), [], False, interpret)


@functools.partial(jax.jit, static_argnames=(
    "eps", "bt", "sub", "hb", "interpret"))
def _out_bwd(o, z, gain, dy, *, eps, bt, sub, hb, interpret):
    """-> ``do``, ``dz``, and the gain's gradient in its dtype."""
    with scope("hvd.gdn.chain"):
        B, _, total = o.shape
        d = gain.shape[0]
        keep = _kept_rows(sub)
        grid, rows, g, lanes = _out_specs(o, gain, bt, hb)
        do, dz, dg = _call(
            "hvd_gdn_chain_out_bwd",
            functools.partial(_out_bwd_kernel, d=d, eps=eps, sub=sub),
            (o, z, lanes, dy), grid, [rows, rows, g, rows],
            [rows, rows, pl.BlockSpec((None, keep, hb * d),
                                      lambda b, c, t: (b, 0, c))],
            [jax.ShapeDtypeStruct(o.shape, o.dtype),
             jax.ShapeDtypeStruct(z.shape, z.dtype),
             jax.ShapeDtypeStruct((B, keep, total), F32)], [], True,
            interpret)
        return do, dz, dg.reshape(-1, d).sum(0).astype(gain.dtype)


def _out_step(o, gain):
    return _tiling(o.shape[1], o.shape[2] // gain.shape[0], gain.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_out(o, z, gain, eps):
    return _out_fwd(o, z, gain, eps=eps, **_out_step(o, gain))


def _kernel_out_fwd(o, z, gain, eps):
    return _kernel_out(o, z, gain, eps), (o, z, gain)


def _kernel_out_bwd(eps, res, dy):
    o, z, gain = res
    return _out_bwd(o, z, gain, dy, eps=eps, **_out_step(o, gain))


_kernel_out.defvjp(_kernel_out_fwd, _kernel_out_bwd)


def chain_out(o, z, gain, eps):
    """Stage two on the kernels: ``o`` [B, T, hv d] as the rule hands it
    (heads side by side), ``z`` beside it, ``gain`` [d], all in the
    compute dtype -> ``RMSNorm(o) * gain * SiLU(z)`` a head.
    Differentiable in all three."""
    return _kernel_out(o, z, gain, float(eps))
