"""What one part does round the streams of hyper-connections (mHC, arXiv
2512.24880; ``models/llama.py:_hyper_connection``), in ONE pass over the
carry ahead of the part and one behind it, forward and backward.

The streams ``X`` [B, n, T, D] in the compute dtype, a part's leaves
``phi`` [n, D, n (n + 2)], ``alpha`` [3], ``bias`` [n (n + 2)]:

- ahead of the part (:func:`_pre`): the ``n`` products ``x_i Phi_i``
  (the compute dtype's operands, float32 accumulation), the RMS over all
  ``n D`` values of a token, ``alpha`` and ``bias``, the two sigmoids,
  ``exp`` of the clamped logits and the Sinkhorn-Knopp iterations, all
  float32 -> the coefficients ``H_pre`` [n], ``H_post`` [n], ``H_res``
  [n, n] a token, and ``u = sum_i H_pre[i] X[i]`` [B, T, D], summed in
  float32 and rounded once;
- behind it (:func:`_post`): ``X'[i] = sum_j H_res[i, j] X[j] +
  H_post[i] y``, summed in float32 and rounded once.

A kernel pair each behind a ``custom_vjp`` that saves its INPUTS (and,
for the second, the coefficients): the backward kernels run the
products, the RMS and the iterations again in VMEM, so no float32
``[B, n, T, D]`` crosses HBM in either direction.

How the blocks lie: as the carry lies, a tile of ``bt`` tokens of all
``n`` streams at full width, ``[n, bt, D]`` with the TOKENS on the
sublanes (any other layout is a copy of the whole carry). The
coefficients are small and live the other way round, TOKENS ON THE LANES:
``[8 (n + 2), T]`` float32, a sublane tile of eight rows a group of ``n``
(``H_pre``, ``H_post``, then a row of ``H_res`` each; rows past ``n``
zero), so that an iteration is a handful of whole-vreg operations: a row
sum is a sum down the sublanes of one group, a column sum the sum of the
``n`` groups. The products come out of the MXU that way round (``Phi``
turned, ``[8 (n + 2), D]`` a stream, against the tile: ``[8 (n + 2),
bt]``); what is summed along ``D`` a token (the squares, ``du . x``, the
cotangents of ``H_post`` and ``H_res``) and what scales a token's row
(the coefficients) crosses between the two worlds as a ``[bt, 128]``
float32 square that turns (``.T``), a value a column. The iterations are
a LOOP in the kernel, their states kept in a VMEM scratch for the
backward: a kernel's body is traced and lowered anew in every process
(twice over in a run of the benchmark), so its set-up is paid by the
equation, and the chain of dependent divisions gains nothing from being
laid out flat (PERF.md section 6, PR 58).

- ``hvd_hc_pre_fwd``: grid ``(B, T / bt)``; reads ``X`` once; writes
  ``u`` and the coefficients.
- ``hvd_hc_post_fwd``: reads ``X``, ``y`` and the coefficients; writes
  ``X'``.
- ``hvd_hc_post_bwd``: from ``dX'``, ``X``, ``y`` and the coefficients:
  ``dy`` and the cotangents of ``H_post`` and ``H_res`` (sums over ``D``
  within the tile).
- ``hvd_hc_pre_bwd``: from ``X``, ``du``, ``dX'`` (read AGAIN: the seam
  below) and the coefficients' cotangents: the forward again, the
  iterations walked backward by hand, the sigmoids, ``alpha`` / ``bias``,
  the RMS and the products; writes ``dX`` with ALL its terms (through
  ``H_res``, through ``u``, through the RMS, through the products)
  summed in float32 and rounded once, and accumulates the leaves'
  gradients in float32 over the (sequential) token axis.

The seam between the pairs. ``X`` reaches ``X'`` along two roads, and
``dX`` is to be rounded once, so ``hvd_hc_pre_bwd`` must see both
cotangents. :func:`_pre` therefore hands the streams on (its third
output, the very array) and :func:`_post` reads THAT; ``_post``'s
backward returns ``dX'`` itself as the cotangent of it, unmixed, and
``_pre``'s applies ``H_res`` transposed to what arrives there. Neither
is a derivative on its own; :func:`hyper_connection` composes them and
is one, and nothing else may call them.

The names are the calls' ``kernel_metadata``, what a device trace shows.
Each kernel sits behind ONE jitted function (a Mosaic lowering a kernel
and phase whatever the layers). :func:`on_kernels` reads the carrier off
the operands (``ops/_platform.py``); elsewhere ``_hyper_connection``'s
``jnp`` expression runs, which is also the tests' reference (the kernels
run there in interpret mode under ``_INTERPRET``).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import _pick_block
from horovod_tpu.ops.gdn_chain import F32, _sigmoid
from horovod_tpu.utils.spans import scope

# Tests flip this to run the kernels in pallas interpret mode on the CPU
# (as ``gdn_chain._INTERPRET``).
_INTERPRET = False
# What one grid step takes: so many tokens at full width, walked so many
# tokens a pass (or the largest divisors under them).
TOKENS_A_STEP = 128
TOKENS_A_PASS = 16
LANES = 128          # a lane slab: D is whole slabs, a tile's tokens too
GROUP = 8            # rows a group of n coefficients: a float32 sublane tile
_SQUARE = 128        # columns of the square that turns
# The kernels keep a tile of every operand in VMEM twice over (Pallas's
# pipeline), far past the 16 MiB a kernel gets unasked.
_VMEM_LIMIT = 100 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))      # a [m, k] by b [n, k] -> [m, n]


class Sizes(NamedTuple):
    """What the model fixes of the arithmetic, read when the program is
    traced (static arguments of the jitted wrappers)."""
    iters: int           # Sinkhorn-Knopp iterations
    eps: float           # beside every row and column sum
    clamp: tuple         # the logits' two edges
    norm_eps: float      # under the root of the mean of squares
    post_scale: float    # H_post = post_scale * sigmoid(.)


def on_kernels(X, sharded=False):
    """True where a part's stream mixing runs as the kernel pairs: the
    streams ``X`` [B, n, T, D] on a TPU (or ``_INTERPRET``, the tests'
    switch), ``D`` whole lane slabs, the tokens whole tiles of whole
    slabs, a group of ``n`` within a sublane tile, and no mesh axis
    dividing the carry (``sharded``: GSPMD cannot partition a Mosaic
    call)."""
    _, n, T, D = X.shape
    return (not sharded and n <= GROUP and D % LANES == 0
            and _pick_block(T, TOKENS_A_STEP) % LANES == 0
            and use_pallas("hc_mix", (X,), _INTERPRET))


def _tiling(T):
    """(tokens a step, tokens a pass), and how it runs."""
    bt = _pick_block(T, TOKENS_A_STEP)
    return {"bt": bt, "sub": _pick_block(bt, TOKENS_A_PASS),
            "interpret": _INTERPRET}


def _rows_of(n):
    """The row of each of the ``n (n + 2)`` coefficients, in the leaves'
    order (``H_pre``, ``H_post``, ``H_res`` row by row), among the
    ``8 (n + 2)`` the kernels keep."""
    k = np.arange(n * (n + 2))
    return GROUP * (k // n) + k % n


def _slotted(phi, alpha, bias, n, dt):
    """The leaves as the kernels read them: ``phi`` turned and laid out
    by rows [n, 8 (n + 2), D] in the compute dtype (zero rows past
    ``n`` in every group), and ``alpha`` (a value a group) and ``bias``
    as two float32 columns [2, 8 (n + 2), 1]."""
    rows, S = _rows_of(n), GROUP * (n + 2)
    turned = jnp.zeros((n, S, phi.shape[1]), dt).at[:, rows].set(
        jnp.swapaxes(phi, 1, 2).astype(dt))
    scale = alpha.astype(F32)[np.repeat(np.arange(3), [n, n, n * n])]
    columns = jnp.zeros((2, S, 1), F32).at[:, rows, 0].set(
        jnp.stack([scale, bias.astype(F32)]))
    return turned, columns


def _call(name, kernel, operands, grid, in_specs, out_specs, out_shape,
          scratch, sequential, interpret):
    """``metadata`` is the name a device trace shows of the call. Grid
    ``(batch, tokens)``; the token axis in order where a step adds to
    what the one before left (``sequential``)."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(*operands)


# ---------------------------------------------------------------------
# Pieces of the kernels' bodies.
# ---------------------------------------------------------------------

def _rows(i, sub):
    """Pass ``i``'s rows of a tile, aligned where a pass is whole packed
    tiles."""
    start = i * sub
    return pl.ds(pl.multiple_of(start, sub) if sub % 16 == 0 else start,
                 sub)


def _total(xs):
    return functools.reduce(lambda a, b: a + b, xs)


def _column(k, at):
    """Column ``at`` of ``k`` [rows, 128] as [rows, 1]: a value a token,
    to scale that token's row."""
    return k[:, at:at + 1]


def _placed(rows, pairs):
    """A [rows, 128] float32 with ``value`` [rows, 1] in column ``at``
    for each ``(at, value)`` and zero elsewhere."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, _SQUARE), 1)
    out = jnp.zeros(lane.shape, F32)
    for at, value in pairs:
        out = jnp.where(lane == at, value, out)
    return out


def _padded(x):
    """[rows, bt] -> [128, bt]: zero rows behind."""
    return jnp.concatenate(
        [x, jnp.zeros((_SQUARE - x.shape[0], x.shape[1]), x.dtype)], axis=0)


def _products(x_ref, phi_ref):
    """``sum_i x_i Phi_i`` of a tile, turned: [S, bt] float32, a
    coefficient a row (``phi_ref``'s), the tokens on the lanes as they
    come out of the MXU."""
    return _total([lax.dot_general(phi_ref[i], x_ref[i], _NT,
                                   preferred_element_type=F32)
                   for i in range(x_ref.shape[0])])


def _kept(n, shape):
    """The rows of a group [8, bt] that hold a value."""
    return lax.broadcasted_iota(jnp.int32, shape, 0) < n


def _below(m, z):
    """What a column step divides by: the groups' sum + eps, and 1 in
    the rows past ``n`` (zeros over ``eps``, twenty times over, are
    zeros over zero once a compiler has made one division of them: XLA
    on the CPU does, under the interpreter)."""
    return jnp.where(_kept(len(m), m[0].shape), _total(m) + z.eps, 1.0)


def _down(x):
    """[8, bt] -> [1, bt]: the sum down the sublanes."""
    return jnp.sum(x, axis=0, keepdims=True)


def _sinkhorn(m, z, states_ref=None):
    """``m``: ``n`` groups [8, bt] (a row of the matrices each, its
    columns down the sublanes, zero past ``n``) -> the same after
    ``z.iters`` iterations: rows over (their sum + eps), then columns
    over (theirs + eps). ``states_ref`` [2 iters, n, 8, bt]: where the
    backward wants every half iteration's input kept."""
    def iteration(k, m):
        if states_ref is not None:
            for i, row in enumerate(m):
                states_ref[2 * k, i] = row
        m = [row / (_down(row) + z.eps) for row in m]
        if states_ref is not None:
            for i, row in enumerate(m):
                states_ref[2 * k + 1, i] = row
        below = _below(m, z)
        return tuple(row / below for row in m)

    return list(lax.fori_loop(0, z.iters, iteration, tuple(m)))


def _sinkhorn_transposed(d, done, z, states_ref):
    """The iterations walked backward: ``d``, the cotangents of ``done``
    (what :func:`_sinkhorn` returned, having kept its states) -> the
    cotangents of what it was given. A half iteration ``b = a / (s +
    eps)``, ``s`` a sum of ``a``'s, transposes to ``da = (db - sum(db
    b)) / (s + eps)``, the sum over what ``s`` summed."""
    n = len(d)

    def iteration(t, carry):
        d, done = carry[:n], carry[n:]
        k = z.iters - 1 - t
        mid = [states_ref[2 * k + 1, i] for i in range(n)]
        inv = 1.0 / _below(mid, z)
        shared = _total([di * bi for di, bi in zip(d, done)])
        d = [(di - shared) * inv for di in d]
        start = [states_ref[2 * k, i] for i in range(n)]
        d = [(di - _down(di * bi)) / (_down(ai) + z.eps)
             for di, bi, ai in zip(d, mid, start)]
        return (*d, *start)

    return list(lax.fori_loop(0, z.iters, iteration, (*d, *done))[:n])


def _coefficients(turned, sb_ref, n, nd, z, states_ref=None):
    """From the square turned, ``turned`` [128, bt] (the products a row,
    the sum of squares in row ``8 (n + 2)``), and the leaves' two
    columns: everything the coefficients are made of, tokens on the
    lanes, float32."""
    S = GROUP * (n + 2)
    proj = turned[:S]
    r = lax.rsqrt(turned[S:S + 1] * (1.0 / nd) + z.norm_eps)      # [1, bt]
    p = proj * r
    raw = p * sb_ref[0] + sb_ref[1]
    pre = _sigmoid(raw[:GROUP])
    half = _sigmoid(raw[GROUP:2 * GROUP])        # H_post over its scale
    logits = [raw[GROUP * (2 + i):GROUP * (3 + i)] for i in range(n)]
    kept = _kept(n, logits[0].shape)
    start = [jnp.where(kept, jnp.exp(jnp.clip(x, *z.clamp)), 0.0)
             for x in logits]
    res = _sinkhorn(start, z, states_ref)
    return {"proj": proj, "r": r, "p": p, "pre": pre, "half": half,
            "logits": logits, "start": start, "res": res}


# ---------------------------------------------------------------------
# Ahead of the part.
# ---------------------------------------------------------------------

def _row_sum(x):
    """[rows, D] -> [rows, 1]: the sum along the lanes."""
    return jnp.sum(x, axis=1, keepdims=True)


def _pre_fwd_kernel(x_ref, phi_ref, sb_ref, u_ref, c_ref, col_ref, *, z,
                    sub):
    n, bt, D = x_ref.shape
    S = GROUP * (n + 2)

    def squares(i, carry):
        at = _rows(i, sub)
        xf = [x_ref[j, at].astype(F32) for j in range(n)]
        col_ref[at] = _placed(sub, [
            (S, _total([_row_sum(x * x) for x in xf]))])
        return carry

    lax.fori_loop(0, bt // sub, squares, 0)
    turned = _padded(_products(x_ref, phi_ref)) + col_ref[...].T
    c = _coefficients(turned, sb_ref, n, n * D, z)
    coef = jnp.concatenate(
        [c["pre"], z.post_scale * c["half"]] + c["res"], axis=0)
    c_ref[...] = coef
    col_ref[...] = _padded(coef).T

    def blend(i, carry):
        at = _rows(i, sub)
        k = col_ref[at]
        u_ref[at] = _total([_column(k, j) * x_ref[j, at].astype(F32)
                            for j in range(n)]).astype(u_ref.dtype)
        return carry

    lax.fori_loop(0, bt // sub, blend, 0)


def _tile_specs(n, bt, D, S):
    """The blocks of a grid step ``(b, t)``: the streams' tile, a
    ``[B, T, D]`` operand's, the coefficients'."""
    return (pl.BlockSpec((None, n, bt, D), lambda b, t: (b, 0, t, 0)),
            pl.BlockSpec((None, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, S, bt), lambda b, t: (b, 0, t)))


def _leaf_specs(n, D, S):
    """The leaves' blocks, the same every step."""
    return [pl.BlockSpec((n, S, D), lambda b, t: (0, 0, 0)),
            pl.BlockSpec((2, S, 1), lambda b, t: (0, 0, 0))]


@functools.partial(jax.jit, static_argnames=("z", "bt", "sub", "interpret"))
def _pre_fwd(X, phi, alpha, bias, *, z, bt, sub, interpret):
    """-> ``u`` [B, T, D] and the coefficients [B, 8 (n + 2), T]. Jitted
    on its own, and the scope again, as ``gated_delta_rule._kernel_fwd``
    has it and says why."""
    with scope("hvd.hc.mix"):
        B, n, T, D = X.shape
        S = GROUP * (n + 2)
        streams, row, coef = _tile_specs(n, bt, D, S)
        return _call(
            "hvd_hc_pre_fwd",
            functools.partial(_pre_fwd_kernel, z=z, sub=sub),
            (X, *_slotted(phi, alpha, bias, n, X.dtype)), (B, T // bt),
            [streams] + _leaf_specs(n, D, S), [row, coef],
            [jax.ShapeDtypeStruct((B, T, D), X.dtype),
             jax.ShapeDtypeStruct((B, S, T), F32)],
            [pltpu.VMEM((bt, _SQUARE), F32)], False, interpret)


def _pre_bwd_kernel(x_ref, phi_ref, sb_ref, du_ref, g_ref, dc_ref, dx_ref,
                    dphi_ref, dsb_ref, col_ref, states_ref, big_ref, *, z,
                    sub):
    """``g_ref``: ``dX'`` as the part behind received it (the module's
    docstring: the seam); ``dc_ref``: the coefficients' cotangents.
    ``dphi_ref`` [n, S, D] and ``dsb_ref`` [2, S, bt] (the raw
    coefficients' cotangents, alone and times what ``alpha`` scaled)
    accumulate over a sequence."""
    n, bt, D = x_ref.shape
    S = GROUP * (n + 2)
    dt = x_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)
        dsb_ref[...] = jnp.zeros_like(dsb_ref)

    def sums(i, carry):
        """A token's sum of squares, and ``H_pre``'s cotangents ``du .
        x_j``."""
        at = _rows(i, sub)
        du = du_ref[at].astype(F32)
        xf = [x_ref[j, at].astype(F32) for j in range(n)]
        col_ref[at] = _placed(
            sub, [(S, _total([_row_sum(x * x) for x in xf]))]
            + [(S + GROUP + j, _row_sum(du * xf[j])) for j in range(n)])
        return carry

    lax.fori_loop(0, bt // sub, sums, 0)
    turned = _padded(_products(x_ref, phi_ref)) + col_ref[...].T
    c = _coefficients(turned, sb_ref, n, n * D, z, states_ref)
    dc = dc_ref[...]
    dres = _sinkhorn_transposed(
        [dc[GROUP * (2 + i):GROUP * (3 + i)] for i in range(n)], c["res"],
        z, states_ref)
    lo, hi = z.clamp
    draw = jnp.concatenate(
        [(dc[:GROUP] + turned[S + GROUP:S + 2 * GROUP])
         * c["pre"] * (1.0 - c["pre"]),
         dc[GROUP:2 * GROUP] * z.post_scale * c["half"] * (1.0 - c["half"])]
        + [jnp.where(jnp.logical_and(x >= lo, x <= hi), d * m, 0.0)
           for x, d, m in zip(c["logits"], dres, c["start"])], axis=0)
    dsb_ref[0] += draw
    dsb_ref[1] += draw * c["p"]
    dp = draw * sb_ref[0]
    dproj = dp * c["r"]                                        # [S, bt]
    # through the RMS: r = (sq / nd + eps)^-1/2, sq = sum x^2
    r = c["r"]
    through_rms = jnp.sum(dp * c["proj"], axis=0, keepdims=True) \
        * (r * r * r) * (-1.0 / (n * D))
    col_ref[...] = jnp.concatenate(
        [c["pre"], jnp.zeros((GROUP, bt), F32)] + c["res"]
        + [jnp.broadcast_to(through_rms, (GROUP, bt)),
           jnp.zeros((_SQUARE - S - GROUP, bt), F32)], axis=0).T
    dproj_dt = dproj.astype(dt)
    back = _padded(dproj).T[:, :S].astype(dt)                  # [bt, S]
    for j in range(n):
        dphi_ref[j] += jnp.dot(dproj_dt, x_ref[j],
                               preferred_element_type=F32)
        big_ref[j] = jnp.dot(back, phi_ref[j], preferred_element_type=F32)

    def gather(i, carry):
        at = _rows(i, sub)
        k = col_ref[at]
        du = du_ref[at].astype(F32)
        g = [g_ref[a, at].astype(F32) for a in range(n)]
        for j in range(n):
            dx = _total([_column(k, GROUP * (2 + a) + j) * g[a]
                         for a in range(n)]) \
                + _column(k, j) * du \
                + _column(k, S) * x_ref[j, at].astype(F32) + big_ref[j, at]
            dx_ref[j, at] = dx.astype(dt)
        return carry

    lax.fori_loop(0, bt // sub, gather, 0)


@functools.partial(jax.jit, static_argnames=("z", "bt", "sub", "interpret"))
def _pre_bwd(X, phi, alpha, bias, du, g, dc, *, z, bt, sub, interpret):
    """-> ``dX`` and the leaves' gradients in their dtypes."""
    with scope("hvd.hc.mix"):
        B, n, T, D = X.shape
        S = GROUP * (n + 2)
        streams, row, coef = _tile_specs(n, bt, D, S)
        dX, dphi, dsb = _call(
            "hvd_hc_pre_bwd",
            functools.partial(_pre_bwd_kernel, z=z, sub=sub),
            (X, *_slotted(phi, alpha, bias, n, X.dtype), du, g, dc),
            (B, T // bt),
            [streams] + _leaf_specs(n, D, S) + [row, streams, coef],
            [streams,
             pl.BlockSpec((None, n, S, D), lambda b, t: (b, 0, 0, 0)),
             pl.BlockSpec((None, 2, S, bt), lambda b, t: (b, 0, 0, 0))],
            [jax.ShapeDtypeStruct(X.shape, X.dtype),
             jax.ShapeDtypeStruct((B, n, S, D), F32),
             jax.ShapeDtypeStruct((B, 2, S, bt), F32)],
            [pltpu.VMEM((bt, _SQUARE), F32),
             pltpu.VMEM((2 * z.iters, n, GROUP, bt), F32),
             pltpu.VMEM((n, bt, D), F32)], True, interpret)
        rows = _rows_of(n)
        dphi = jnp.swapaxes(dphi.sum(0)[:, rows], 1, 2).astype(phi.dtype)
        draw, scaled = dsb.sum((0, 3))[:, rows]
        group = np.repeat(np.arange(3), [n, n, n * n])
        dalpha = jnp.zeros((3,), F32).at[group].add(scaled)
        return dX, dphi, dalpha.astype(alpha.dtype), draw.astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(X, phi, alpha, bias, z):
    """-> (``u``, the coefficients, the streams handed on to
    :func:`_post`)."""
    return (*_pre_fwd(X, phi, alpha, bias, z=z, **_tiling(X.shape[2])), X)


def _pre_vjp_fwd(X, phi, alpha, bias, z):
    return _pre(X, phi, alpha, bias, z), (X, phi, alpha, bias)


def _pre_vjp_bwd(z, saved, cotangents):
    du, dcoef, g = cotangents      # ``g``: ``dX'`` (the seam)
    return _pre_bwd(*saved, du, g, dcoef, z=z,
                    **_tiling(saved[0].shape[2]))


# ---------------------------------------------------------------------
# Behind the part.
# ---------------------------------------------------------------------

def _post_fwd_kernel(c_ref, x_ref, y_ref, o_ref, col_ref, *, sub):
    n, bt, D = x_ref.shape
    col_ref[...] = _padded(c_ref[...]).T

    def mix(i, carry):
        at = _rows(i, sub)
        k = col_ref[at]
        x = [x_ref[j, at].astype(F32) for j in range(n)]
        y = y_ref[at].astype(F32)
        for a in range(n):
            out = _total([_column(k, GROUP * (2 + a) + j) * x[j]
                          for j in range(n)]) + _column(k, GROUP + a) * y
            o_ref[a, at] = out.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, bt // sub, mix, 0)


@functools.partial(jax.jit, static_argnames=("bt", "sub", "interpret"))
def _post_fwd(coef, X, y, *, bt, sub, interpret):
    with scope("hvd.hc.mix"):
        B, n, T, D = X.shape
        streams, row, c = _tile_specs(n, bt, D, coef.shape[1])
        return _call(
            "hvd_hc_post_fwd", functools.partial(_post_fwd_kernel, sub=sub),
            (coef, X, y), (B, T // bt), [c, streams, row], streams,
            jax.ShapeDtypeStruct(X.shape, X.dtype),
            [pltpu.VMEM((bt, _SQUARE), F32)], False, interpret)


def _post_bwd_kernel(c_ref, x_ref, y_ref, g_ref, dy_ref, dc_ref, col_ref,
                     sums_ref, *, sub):
    n, bt, D = x_ref.shape
    S = c_ref.shape[0]
    col_ref[...] = _padded(c_ref[...]).T

    def transpose(i, carry):
        at = _rows(i, sub)
        k = col_ref[at]
        x = [x_ref[j, at].astype(F32) for j in range(n)]
        y = y_ref[at].astype(F32)
        g = [g_ref[a, at].astype(F32) for a in range(n)]
        dy_ref[at] = _total([_column(k, GROUP + a) * g[a]
                             for a in range(n)]).astype(dy_ref.dtype)
        sums_ref[at] = _placed(
            sub, [(GROUP + a, _row_sum(g[a] * y)) for a in range(n)]
            + [(GROUP * (2 + a) + j, _row_sum(g[a] * x[j]))
               for a in range(n) for j in range(n)])
        return carry

    lax.fori_loop(0, bt // sub, transpose, 0)
    dc_ref[...] = sums_ref[...].T[:S]


@functools.partial(jax.jit, static_argnames=("bt", "sub", "interpret"))
def _post_bwd(coef, X, y, g, *, bt, sub, interpret):
    """-> ``dy`` and the coefficients' cotangents (``H_pre``'s rows
    zero: ``hvd_hc_pre_bwd`` has ``du``)."""
    with scope("hvd.hc.mix"):
        B, n, T, D = X.shape
        streams, row, c = _tile_specs(n, bt, D, coef.shape[1])
        return _call(
            "hvd_hc_post_bwd", functools.partial(_post_bwd_kernel, sub=sub),
            (coef, X, y, g), (B, T // bt), [c, streams, row, streams],
            [row, c],
            [jax.ShapeDtypeStruct(y.shape, y.dtype),
             jax.ShapeDtypeStruct(coef.shape, F32)],
            [pltpu.VMEM((bt, _SQUARE), F32)] * 2, False, interpret)


@jax.custom_vjp
def _post(coef, X, y):
    return _post_fwd(coef, X, y, **_tiling(X.shape[2]))


def _post_vjp_fwd(coef, X, y):
    return _post(coef, X, y), (coef, X, y)


def _post_vjp_bwd(saved, g):
    """``g`` itself stands where ``X``'s cotangent belongs: the module's
    docstring, the seam."""
    dy, dcoef = _post_bwd(*saved, g, **_tiling(saved[1].shape[2]))
    return dcoef, g, dy


_pre.defvjp(_pre_vjp_fwd, _pre_vjp_bwd)
_post.defvjp(_post_vjp_fwd, _post_vjp_bwd)


# ---------------------------------------------------------------------
# What the model calls.
# ---------------------------------------------------------------------

def hyper_connection(X, phi, alpha, bias, part, sizes):
    """One part round the streams on the kernels: ``X`` [B, n, T, D] in
    the compute dtype, the part's three leaves, ``part`` ([B, T, D] ->
    (its output, its aux)), ``sizes`` a :class:`Sizes` -> (``X'``, the
    aux). Differentiable in ``X``, the leaves and whatever ``part``
    closes over."""
    with scope("hvd.hc.mix"):
        u, coef, streams = _pre(X, phi, alpha, bias, sizes)
    y, aux = part(u)
    with scope("hvd.hc.mix"):
        return _post(coef, streams, y.astype(X.dtype)), aux


def coefficients(X, phi, alpha, bias, sizes):
    """What ``hvd_hc_pre_fwd`` computed of one part's coefficients, in
    ``_hc_coefficients``' shapes: float32 (``H_pre`` [B, n, T],
    ``H_post`` [B, n, T], ``H_res`` [B, n, n, T])."""
    B, n, T, _ = X.shape
    coef = _pre(X, phi, alpha, bias, sizes)[1]
    groups = coef.reshape(B, n + 2, GROUP, T)[:, :, :n]
    return groups[:, 0], groups[:, 1], groups[:, 2:]
