"""The elementwise chain round the SSD recurrence of a Mamba-2 mixer
(``models/llama.py:_mamba2``), each of its two stages in ONE pass over
HBM forward and one backward: ``ops/gdn_chain.py``'s passes (its tiling,
halo, pass loop and the convolution's transpose are imported, not
copied) with what differs between the two mixers read off the shapes.

Stage one, before the recurrence (:func:`chain_in`): from ``zxr`` [B, T,
2 di + 2 G N + H] as the input projection leaves it, ``[z, X, B, C, r]``
side by side, the taps [taps, di + 2 G N] and their bias: the depthwise
causal convolution summed in float32, the bias, SiLU in float32, the
cast to the compute dtype -> ``X`` [B, T, di], ``B``, ``C`` [B, T, G N],
and ``z`` [B, T, di], ``r`` [B, T, H] as they stand. Stage two, behind
it (:func:`chain_out`): the gate FIRST (the delta rule's mixer norms
first), ``RMSNorm_group(y * SiLU(z)) * gain`` with ``y``, ``z`` [B, T,
di], the mean of squares over each of the ``G`` groups' ``di / G``
channels, everything up to the one rounding in float32, the gain [di] a
channel.

A kernel pair a stage behind a ``custom_vjp`` that saves its INPUTS and
nothing else: the backward kernels run the convolution, SiLU, the gate
and the norm again in VMEM, so no float32 ``[B, T, di + 2 G N]`` or
``[B, T, di]`` crosses HBM for them. The blocks lie as the projections'
matmuls leave and take their operands and as ``ops/ssd.py`` reads ``x``
and writes ``y``: ``[B, T, columns]`` with the TOKENS on the sublanes
(``[B, T, G, di / G]`` puts the groups there: a copy of the whole array
on a TPU). A group's statistic is a sum over the lanes of its columns,
a row at a time.

- ``hvd_ssd_chain_in_fwd``: grid ``(B, n, T / bt)``, every axis
  parallel; a step takes a ``1 / n`` of ``X``'s columns, of ``B``'s and
  of ``C``'s (windows on the one ``zxr``, no split copy), and beside
  each tile the ``halo`` tokens before it (zeros before token 0).
- ``hvd_ssd_chain_in_bwd``: grid ``(B, columns / W, T / bt)`` over
  ``zxr``'s columns as they lie, so that ONE output holds the whole of
  ``d zxr`` (``dz`` and ``dr`` copied into their columns, ``r``'s the
  last block's first ``H``: no concatenate behind the kernel); the
  token axis sequential and counting DOWN as in
  ``hvd_gdn_chain_in_bwd``; the taps' and the bias's gradients
  accumulate over a sequence in float32 and are summed outside.
- ``hvd_ssd_chain_out_fwd`` / ``_bwd``: grid ``(B, G / gb, T / bt)``, a
  step ``gb`` whole groups; the backward accumulates the gain's gradient
  over the (then sequential) token axis.

The names are the calls' ``kernel_metadata``. Each kernel sits behind ONE
jitted function (a lowering a kernel whatever the layers and phases).
:func:`on_kernels` reads the carrier off the operands
(``ops/_platform.py``) and the shapes: a group, ``G N`` or ``di`` that is
no whole number of lane slabs (:data:`LANES`), or whose column windows
would not start on a block's edge, TAKES THE EXPRESSION of
``models/llama.py:_mamba2`` (which is also the tests' reference; the
kernels run there in interpret mode under ``_INTERPRET``); taps that
reach past a tile of tokens are REFUSED by name (``gdn_chain._halo``).
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
from horovod_tpu.ops.gdn_chain import (
    F32, _call, _fill, _halo, _head_sums, _kept_rows, _partial_sums, _rows,
    _sigmoid, _silu_grad, _taps_sum, _taps_transpose)
from horovod_tpu.utils.spans import scope

# Tests flip this to run the kernels in pallas interpret mode on the CPU
# (as ``gdn_chain._INTERPRET``).
_INTERPRET = False
# What one grid step takes: so many tokens by so many lanes (or the
# largest divisors under them), walked so many tokens a pass.
TOKENS_A_STEP = 256
LANES_A_STEP = 1024
TOKENS_A_PASS = 32
LANES = 128          # a lane slab: every block is whole slabs wide


def _columns(di, gn):
    """How the kernels split ``zxr``'s columns: (the forward's steps
    ``n``, the backward's block width ``W``), or None where they cannot:
    ``W`` is whole lane slabs (``LANES_A_STEP`` lanes or the most under
    it) that divide ``z``'s, ``X``'s, ``B``'s and ``C``'s columns; a
    forward step takes ``di / n`` of ``X`` (the most under
    ``LANES_A_STEP``, else the least above it) and ``G N / n`` of ``B``
    and of ``C``, each window starting on an edge of its own blocks."""
    across = math.gcd(di, gn)
    W = 0 if across % LANES else LANES * _pick_block(
        across // LANES, max(LANES_A_STEP // LANES, 1))
    steps = [n for n in range(1, gn // LANES + 1)
             if di % n == 0 and gn % n == 0 and (di // n) % LANES == 0
             and (gn // n) % LANES == 0 and di % (gn // n) == 0]
    if not (W and steps):
        return None
    return next((n for n in steps if di // n <= LANES_A_STEP), steps[-1]), W


def on_kernels(x, di, groups, gn):
    """True where the chain runs as the kernel pairs: the mixer's input
    ``x`` on a TPU (or ``_INTERPRET``, the tests' switch) and columns
    the kernels can tile (the module's docstring)."""
    return bool(_columns(di, gn)) and di % (groups * LANES) == 0 \
        and use_pallas("ssd_chain", (x,), _INTERPRET)


def _tiling(T):
    """(tokens a step, tokens a pass), and how it runs."""
    bt = _pick_block(T, TOKENS_A_STEP)
    return {"bt": bt, "sub": _pick_block(bt, TOKENS_A_PASS),
            "interpret": _INTERPRET}


# ---------------------------------------------------------------------
# Stage one: taps, bias, SiLU.
# ---------------------------------------------------------------------

def _conv_act(ext_ref, w, bias, i, sub, h):
    """Pass ``i`` of a tile: (its tokens as they were 0, 1, ... tokens
    ago, the convolution plus its bias, the sigmoid of that), all
    float32: ``_mamba2`` rounds behind SiLU only."""
    ago, conv = _taps_sum(ext_ref, w, i, sub, h)
    c = conv + bias
    return ago, c, _sigmoid(c)


def _in_fwd_kernel(*refs, sub):
    """``refs``: the tiles of ``X``, ``B``, ``C`` in ``zxr``, their
    halos, their taps, their biases; ``X``, ``B``, ``C`` out; a scratch
    a width."""
    first = pl.program_id(2) == 0
    wide, narrow = refs[15:]

    def section(x_ref, halo_ref, w_ref, b_ref, out_ref, ext_ref):
        h = halo_ref.shape[0]
        _fill(ext_ref, x_ref, halo_ref, first)
        w, bias = w_ref[...].astype(F32), b_ref[...].astype(F32)

        def one_pass(i, carry):
            _, c, s = _conv_act(ext_ref, w, bias, i, sub, h)
            out_ref[_rows(i, sub)] = (c * s).astype(out_ref.dtype)
            return carry

        lax.fori_loop(0, out_ref.shape[0] // sub, one_pass, 0)

    for j, ext_ref in enumerate((wide, narrow, narrow)):   # X, B, C
        section(*refs[j:15:3], ext_ref)


@functools.partial(jax.jit, static_argnames=(
    "di", "gn", "n", "bt", "halo", "sub", "interpret"))
def _in_fwd(zxr, taps, bias, *, di, gn, n, bt, halo, sub, interpret):
    """-> ``X`` [B, T, di], ``B``, ``C`` [B, T, gn], ``z``, ``r``.
    Jitted on its own, and the scope again, as
    ``gated_delta_rule._kernel_fwd`` has it and says why."""
    with scope("hvd.ssd.chain"):
        B, T, _ = zxr.shape
        wx, wb = di // n, gn // n
        # (a window's width, the block of that width where it starts):
        # zxr's X, B, C, then the taps' and the bias's
        inside = [(wx, di // wx), (wb, 2 * di // wb),
                  (wb, (2 * di + gn) // wb)]
        beside = [(wx, 0), (wb, di // wb), (wb, (di + gn) // wb)]

        def spec(tokens, token_block):
            return [pl.BlockSpec(
                (None, tokens, w),
                lambda b, c, t, at=at: (b, token_block(t), at + c))
                for w, at in inside]

        def row(rows):
            return [pl.BlockSpec((rows, w), lambda b, c, t, at=at: (0, at + c))
                    for w, at in beside]

        X, Bm, Cm = _call(
            "hvd_ssd_chain_in_fwd", functools.partial(_in_fwd_kernel, sub=sub),
            [zxr] * 6 + [taps] * 3 + [bias[None]] * 3, (B, n, T // bt),
            spec(bt, lambda t: t)
            + spec(halo, lambda t: jnp.maximum(t * (bt // halo) - 1, 0))
            + row(taps.shape[0]) + row(1),
            [pl.BlockSpec((None, bt, w), lambda b, c, t: (b, t, c))
             for w in (wx, wb, wb)],
            [jax.ShapeDtypeStruct((B, T, w), zxr.dtype)
             for w in (di, gn, gn)],
            [pltpu.VMEM((halo + bt, w), F32) for w in (wx, wb)], False,
            interpret)
        return X, Bm, Cm, zxr[:, :, :di], zxr[:, :, 2 * (di + gn):]


def _in_bwd_kernel(x_ref, halo_ref, w_ref, b_ref, dX_ref, dB_ref, dC_ref,
                   dz_ref, dr_ref, dx_ref, dw_ref, db_ref, ext_ref,
                   next_ref, *, edges, sub):
    """A column block of ``d zxr``: grid step ``t`` holds token tile
    ``T / bt - 1 - t``. ``edges``: the first block of ``X``, of ``B``,
    of ``C`` and of ``r``. ``next_ref`` and the taps' gradient as in
    ``gdn_chain._in_bwd_kernel``; the bias's accumulates beside it."""
    c, t = pl.program_id(1), pl.program_id(2)
    bt, h = dx_ref.shape[0], halo_ref.shape[0]
    at_x, at_b, at_c, at_r = edges

    def section(d_ref):
        _fill(ext_ref, x_ref, halo_ref, t == pl.num_programs(2) - 1)

        @pl.when(t == 0)
        def _start():
            next_ref[...] = jnp.zeros_like(next_ref)
            dw_ref[...] = jnp.zeros_like(dw_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        w, bias = w_ref[...].astype(F32), b_ref[...].astype(F32)
        passes = bt // sub

        def one_pass(j, after):
            i = passes - 1 - j
            ago, c_, s = _conv_act(ext_ref, w, bias, i, sub, h)
            dconv = d_ref[_rows(i, sub)].astype(F32) * _silu_grad(c_, s)
            db_ref[...] += _partial_sums(dconv)
            return _taps_transpose(dconv, after, ago, w, dx_ref, dw_ref, i,
                                   sub)

        next_ref[...] = lax.fori_loop(0, passes, one_pass, next_ref[...])

    @pl.when(c < at_x)
    def _z():
        dx_ref[...] = dz_ref[...]

    pl.when(jnp.logical_and(c >= at_x, c < at_b))(lambda: section(dX_ref))
    pl.when(jnp.logical_and(c >= at_b, c < at_c))(lambda: section(dB_ref))
    pl.when(jnp.logical_and(c >= at_c, c < at_r))(lambda: section(dC_ref))

    @pl.when(c >= at_r)
    def _r():
        dx_ref[...] = dr_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "di", "gn", "W", "bt", "halo", "sub", "interpret"))
def _in_bwd(zxr, taps, bias, dX, dB, dC, dz, dr, *, di, gn, W, bt, halo,
            sub, interpret):
    """-> ``d zxr`` whole, the taps' and the bias's gradients in their
    dtypes."""
    with scope("hvd.ssd.chain"):
        B, T, total = zxr.shape
        ntaps = taps.shape[0]
        at_x, nb = di // W, gn // W
        at_b = 2 * at_x
        at_c, at_r, end = at_b + nb, at_b + 2 * nb, -(-total // W)
        last, keep = T // bt - 1, _kept_rows(sub)

        def tile(first, stop, tokens=bt, token_block=lambda t: last - t,
                 offset=0):
            """The column blocks [first, stop) of an operand whose block
            0 is the grid's block ``first - offset``; any other block
            parks on block 0 (fetched once, as long as the index
            stands)."""
            def index(b, c, t):
                mine = jnp.logical_and(c >= first, c < stop)
                return tuple(jnp.where(mine, i, 0) for i in (
                    b, token_block(t), c - first + offset))
            return pl.BlockSpec((None, tokens, W), index)

        def conv_row(rows):   # a block of the taps or of the bias
            return pl.BlockSpec((rows, W), lambda b, c, t: (
                0, jnp.clip(c - at_x, 0, at_r - at_x - 1)))

        def sums(rows):       # the partial sums of their gradients
            return pl.BlockSpec((None,) + rows + (W,), lambda b, c, t: (
                (b,) + (0,) * len(rows)
                + (jnp.clip(c - at_x, 0, at_r - at_x - 1),)))

        dx, dw, db = _call(
            "hvd_ssd_chain_in_bwd",
            functools.partial(_in_bwd_kernel, edges=(at_x, at_b, at_c, at_r),
                              sub=sub),
            (zxr, zxr, taps, bias[None], dX, dB, dC, dz, dr),
            (B, end, T // bt),
            [tile(at_x, at_r, offset=at_x),
             tile(at_x, at_r, halo, lambda t: jnp.maximum(
                 (last - t) * (bt // halo) - 1, 0), offset=at_x),
             conv_row(ntaps), conv_row(1),
             tile(at_x, at_b), tile(at_b, at_c), tile(at_c, at_r),
             tile(0, at_x), tile(at_r, end)],
            [pl.BlockSpec((None, bt, W), lambda b, c, t: (b, last - t, c)),
             sums((ntaps, keep)), sums((keep,))],
            [jax.ShapeDtypeStruct(zxr.shape, zxr.dtype),
             jax.ShapeDtypeStruct((B, ntaps, keep, taps.shape[1]), F32),
             jax.ShapeDtypeStruct((B, keep, taps.shape[1]), F32)],
            [pltpu.VMEM((halo + bt, W), F32), pltpu.VMEM((halo, W), F32)],
            True, interpret)
        return dx, dw.sum((0, 2)).astype(taps.dtype), \
            db.sum((0, 1)).astype(bias.dtype)


def _in_step(zxr, taps, di, gn):
    step = _tiling(zxr.shape[1])
    return {"di": di, "gn": gn, "halo": _halo(step["bt"], taps.shape[0]),
            **step}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_in(zxr, taps, bias, di, gn):
    return _in_fwd(zxr, taps, bias, n=_columns(di, gn)[0],
                   **_in_step(zxr, taps, di, gn))


def _kernel_in_fwd(zxr, taps, bias, di, gn):
    return _kernel_in(zxr, taps, bias, di, gn), (zxr, taps, bias)


def _kernel_in_bwd(di, gn, res, grads):
    zxr, taps, bias = res
    return _in_bwd(zxr, taps, bias, *grads, W=_columns(di, gn)[1],
                   **_in_step(zxr, taps, di, gn))


_kernel_in.defvjp(_kernel_in_fwd, _kernel_in_bwd)


def chain_in(zxr, taps, bias, d_inner, group_states):
    """Stage one on the kernels: ``zxr`` [B, T, 2 di + 2 gn + H] in the
    compute dtype, the taps [taps, di + 2 gn] and their bias [di + 2 gn]
    (None: no bias) -> ``X`` [B, T, di], ``B``, ``C`` [B, T, gn]
    (convolved, SiLU), ``z`` [B, T, di] and ``r`` [B, T, H] as they
    stand. Differentiable in all three."""
    if bias is None:
        bias = jnp.zeros(taps.shape[1:], taps.dtype)
    return _kernel_in(zxr, taps, bias, d_inner, group_states)


# ---------------------------------------------------------------------
# Stage two: the gate, then the norm a group.
# ---------------------------------------------------------------------

def _gate_norm(y_ref, z_ref, at, d, eps):
    """A pass's rows of ``RMSNorm_group(y * SiLU(z))`` before the
    rounding, and every value the backward reads again, all float32:
    ``y``, ``z``, its sigmoid, the gated ``g / rms`` and ``1 / rms``."""
    y, z = y_ref[at].astype(F32), z_ref[at].astype(F32)
    s = _sigmoid(z)
    g = y * (z * s)
    rs = lax.rsqrt(_head_sums(g * g, d) * (1.0 / d) + eps)
    return y, z, s, g * rs, rs


def _out_fwd_kernel(y_ref, z_ref, g_ref, o_ref, *, d, eps, sub):
    dt = o_ref.dtype
    gain = g_ref[...].astype(F32)

    def one_pass(i, carry):
        at = _rows(i, sub)
        *_, u, _ = _gate_norm(y_ref, z_ref, at, d, eps)
        o_ref[at] = (u.astype(dt).astype(F32) * gain).astype(dt)
        return carry

    lax.fori_loop(0, y_ref.shape[0] // sub, one_pass, 0)


def _out_bwd_kernel(y_ref, z_ref, g_ref, do_ref, dy_ref, dz_ref, dg_ref, *,
                    d, eps, sub):
    dt = y_ref.dtype
    gain = g_ref[...].astype(F32)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    def one_pass(i, carry):
        at = _rows(i, sub)
        y, z, s, u, rs = _gate_norm(y_ref, z_ref, at, d, eps)
        do = do_ref[at].astype(F32)
        dg_ref[...] += _partial_sums(do * u.astype(dt).astype(F32))
        du = (do * gain).astype(dt).astype(F32)
        dgated = rs * (du - u * _head_sums(du * u, d) * (1.0 / d))
        dy_ref[at] = (dgated * (z * s)).astype(dt)
        dz_ref[at] = (dgated * y * _silu_grad(z, s)).astype(dt)
        return carry

    lax.fori_loop(0, y_ref.shape[0] // sub, one_pass, 0)


def _out_specs(y, bt, width):
    """(grid, a block of rows, the gain's block) for ``y`` [B, T, di]
    by steps of ``width`` lanes."""
    B, T, di = y.shape
    return (B, di // width, T // bt), \
        pl.BlockSpec((None, bt, width), lambda b, c, t: (b, t, c)), \
        pl.BlockSpec((1, width), lambda b, c, t: (0, c))


@functools.partial(jax.jit, static_argnames=(
    "d", "eps", "width", "bt", "sub", "interpret"))
def _out_fwd(y, z, gain, *, d, eps, width, bt, sub, interpret):
    with scope("hvd.ssd.chain"):
        grid, rows, g = _out_specs(y, bt, width)
        return _call(
            "hvd_ssd_chain_out_fwd",
            functools.partial(_out_fwd_kernel, d=d, eps=eps, sub=sub),
            (y, z, gain[None]), grid, [rows, rows, g], rows,
            jax.ShapeDtypeStruct(y.shape, y.dtype), [], False, interpret)


@functools.partial(jax.jit, static_argnames=(
    "d", "eps", "width", "bt", "sub", "interpret"))
def _out_bwd(y, z, gain, do, *, d, eps, width, bt, sub, interpret):
    """-> ``dy``, ``dz``, and the gain's gradient in its dtype."""
    with scope("hvd.ssd.chain"):
        B, _, di = y.shape
        keep = _kept_rows(sub)
        grid, rows, g = _out_specs(y, bt, width)
        dy, dz, dg = _call(
            "hvd_ssd_chain_out_bwd",
            functools.partial(_out_bwd_kernel, d=d, eps=eps, sub=sub),
            (y, z, gain[None], do), grid, [rows, rows, g, rows],
            [rows, rows, pl.BlockSpec((None, keep, width),
                                      lambda b, c, t: (b, 0, c))],
            [jax.ShapeDtypeStruct(y.shape, y.dtype),
             jax.ShapeDtypeStruct(z.shape, z.dtype),
             jax.ShapeDtypeStruct((B, keep, di), F32)], [], True,
            interpret)
        return dy, dz, dg.sum((0, 1)).astype(gain.dtype)


def _out_step(y, groups, eps):
    """A step is whole groups of ``d`` channels, ``LANES_A_STEP`` lanes
    of them or one group."""
    d = y.shape[2] // groups
    return {"d": d, "eps": eps, **_tiling(y.shape[1]),
            "width": d * _pick_block(groups, max(LANES_A_STEP // d, 1))}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_out(y, z, gain, groups, eps):
    return _out_fwd(y, z, gain, **_out_step(y, groups, eps))


def _kernel_out_fwd(y, z, gain, groups, eps):
    return _kernel_out(y, z, gain, groups, eps), (y, z, gain)


def _kernel_out_bwd(groups, eps, res, do):
    y, z, gain = res
    return _out_bwd(y, z, gain, do, **_out_step(y, groups, eps))


_kernel_out.defvjp(_kernel_out_fwd, _kernel_out_bwd)


def chain_out(y, z, gain, groups, eps):
    """Stage two on the kernels: ``y`` [B, T, di] as the recurrence
    hands it (heads side by side), ``z`` beside it, ``gain`` [di], all
    in the compute dtype -> ``RMSNorm(y * SiLU(z)) * gain``, the norm
    over each of ``groups`` runs of ``di / groups`` channels.
    Differentiable in all three."""
    return _kernel_out(y, z, gain, groups, float(eps))
