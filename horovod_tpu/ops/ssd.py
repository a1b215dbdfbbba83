"""The state-space recurrence of a Mamba-2 layer (SSD: Dao & Gu,
arXiv:2405.21060, sections 5-6), forward and backward.

A head ``h`` of ``H`` carries a state ``S`` in ``R^{P x N}`` (``P``
channels, ``N`` states); its decay is ONE scalar a head and token, and
the ``H / G`` heads of a group read the same ``B_t``, ``C_t`` in
``R^N``. From ``S_0 = 0``, a token at a time::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

A scalar decay has a matmul form (Mamba-1's decay a channel and state
has none: ``ops/selective_scan.py``). Over a chunk of ``L`` tokens, with
``a_t = dt_t A`` and ``gamma`` its running sum inside the chunk, ``S``
the state the chunk starts from::

    y  = ((C B^T) * exp(gamma_i - gamma_j) * [i >= j]) (dt x)
         + exp(gamma_i) C S^T
    S <- exp(gamma_L) S + (dt x * exp(gamma_L - gamma))^T B

Every exponent is a difference of running sums of non-positive numbers:
nothing divides by a running product. ``C B^T`` is one ``[L, L]``
product a group and chunk, shared by its heads. This is the gated delta
rule (``ops/gated_delta_rule.py``) without its WY correction: no
inverse, no ``triangular_solve``.

One recurrence, two carriers; which runs is read off the operands
(``ops/_platform.py``), never off an option:

- operands on a TPU: a Pallas kernel pair, ``hvd_ssd_fwd`` /
  ``hvd_ssd_bwd`` by their ``kernel_metadata``. The grid is ``(B, H /
  hb, T / L)``, the chunks last and sequential; a step is one chunk of
  ``hb`` heads (a whole group's where VMEM takes it, so that ``B``,
  ``C`` and ``C B^T`` are read and formed once for the group), whose
  states, ``[hb P, N]`` float32, live in a VMEM scratch for the whole
  sequence. ``x`` and ``y`` are read and written where the projections
  leave them, ``[B, T, H P]`` with tokens on the sublanes; a step works
  on slabs of 128 lanes (two heads of 64 channels side by side: one
  matmul serves both, and what differs a head is chosen by a select on
  the lane; a head of 128 channels is a slab alone, and where a group
  is ONE head, Lightning Attention's ``G = H``, a step holds that head
  and its ``dB`` / ``dC`` leave in the operands' dtype, with no
  float32 parts to sum). ``dt`` and ``gamma`` cross HBM lane-dense, ``[B, H, T / L,
  L]`` float32, a sequence's block staying in VMEM, and are turned down
  the sublanes in the kernel (``gated_delta_rule._down``). The forward
  writes ``y`` and, where they are kept, the state each chunk STARTED
  from (``T / L x [H P, N]`` float32: 268 MB at T 8192, H 128, P 64, N
  128, alive for one layer's backward under the layer's checkpoint).
  The backward walks the chunks in reverse with the state's cotangent in
  the scratch, forms the chunk's decays again and gives ``dx``, ``ddt``,
  ``dgamma``, ``dB``, ``dC`` (the last two summed over a step's heads).
  Each kernel sits behind ONE jitted function, so that a program lowers
  the forward twice and the backward once whatever its depth
  (``ops/gated_delta_rule.py`` says why); the device scope its
  instructions carry is the calling mixer's (``where``: a Mamba-2
  layer's ``hvd.ssd.core``, a lightning layer's ``hvd.lightning.core``);
- elsewhere: ``_scan_core``, a ``lax.scan`` over chunks of the same
  chunked form in ``jax.numpy`` under a ``custom_vjp`` that keeps the
  chunk-boundary states and runs one reverse pass (the CPU's path and
  the tests' reference for the kernels, which run there in interpret
  mode under ``_INTERPRET``).

``A``, ``D`` and the running sum stay outside both: ``gamma`` is a
``cumsum`` of ``dt A`` (4 MB a layer) and ``D x`` an elementwise pass,
which autodiff differentiates.

Precision: ``dt``, ``gamma``, every exponential, the state and every
accumulation are float32; the matmuls take operands in ``x``'s dtype
(the decayed scores, ``dt x`` and the state rounded to it as they enter
one) and accumulate in float32. Both carriers round at the same places.
"""

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import _pick_block
from horovod_tpu.ops.gated_delta_rule import (
    F32, _along, _down, _eye, _mm, _rowsum)
from horovod_tpu.utils.spans import scope

CHUNK = 128
# Tests flip this to run the kernel pair in pallas interpret mode on the
# CPU (as ``gated_delta_rule._INTERPRET``).
_INTERPRET = False
# Heads a grid step takes: a group's, or the largest divisor of a
# group's heads under this.
HEADS_A_STEP = 16
LANES = 128
# What the kernels may hold in VMEM: the backward's ``[hb, L, L]``
# float32 decays, scores and their cotangents, 1 MB each at sixteen
# heads, beside a chunk's blocks twice.
VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------
# The chunked form in jax.numpy: the CPU's path.
# ---------------------------------------------------------------------

def _chunk(S, xs):
    """One chunk from the state ``S`` [B, G, R, P, N] float32 it starts
    with -> (the state it ends with, ``y`` [B, L, G, R, P] float32).
    ``x`` [B, L, G, R, P]; ``dt``, ``gamma`` [B, L, G, R] float32;
    ``Bm``, ``Cm`` [B, L, G, N]."""
    x, dt, gamma, Bm, Cm = xs
    dtype, L = x.dtype, x.shape[1]
    i, j = _eye(L)
    g = jnp.moveaxis(gamma, 1, -1)                       # [B, G, R, L]
    decay = jnp.exp(jnp.where(i >= j, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    cb = _mm("bign,bjgn->bgij", Cm, Bm)
    p = (cb[:, :, None] * decay).astype(dtype)           # [B, G, R, L, L]
    xf = x.astype(F32)
    xd = (xf * dt[..., None]).astype(dtype)
    y = _mm("bgrij,bjgrp->bigrp", p, xd) + jnp.exp(gamma)[..., None] \
        * _mm("bign,bgrpn->bigrp", Cm, S.astype(dtype))
    last = gamma[:, -1]                                  # [B, G, R]
    xdk = (xf * (dt * jnp.exp(last[:, None] - gamma))[..., None]
           ).astype(dtype)
    S = jnp.exp(last)[..., None, None] * S \
        + _mm("bjgrp,bjgn->bgrpn", xdk, Bm)
    return S, y


def _chunk_major(x, dt, gamma, Bm, Cm, chunk):
    """``x`` [B, T, H, P], ``dt``, ``gamma`` [B, T, H], ``Bm``, ``Cm``
    [B, T, G, N] -> the scan's operands, [T / chunk, B, chunk, G, ...]."""
    B, T, H, P = x.shape
    G = Bm.shape[2]

    def lead(a, *rest):
        return jnp.moveaxis(a.reshape(B, T // chunk, chunk, *rest), 1, 0)

    return (lead(x, G, H // G, P), lead(dt, G, H // G),
            lead(gamma, G, H // G), lead(Bm, G, -1), lead(Cm, G, -1))


def _zero_state(xs):
    x, Bm = xs[0], xs[3]
    return jnp.zeros(x.shape[1:2] + x.shape[3:] + Bm.shape[-1:], F32)


def _token_major(y, like):
    """The scan's ``y`` [T / chunk, B, chunk, G, R, P] -> ``like``'s
    shape and dtype."""
    return jnp.moveaxis(y, 0, 1).reshape(like.shape).astype(like.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_core(x, dt, gamma, Bm, Cm, chunk):
    xs = _chunk_major(x, dt, gamma, Bm, Cm, chunk)
    _, y = lax.scan(_chunk, _zero_state(xs), xs)
    return _token_major(y, x)


def _scan_core_fwd(x, dt, gamma, Bm, Cm, chunk):
    xs = _chunk_major(x, dt, gamma, Bm, Cm, chunk)

    def step(S, x):
        S_next, y = _chunk(S, x)
        return S_next, (y, S)

    _, (y, states) = lax.scan(step, _zero_state(xs), xs)
    return _token_major(y, x), (x, dt, gamma, Bm, Cm, states)


def _scan_core_bwd(chunk, res, dy):
    *operands, states = res
    x = operands[0]
    xs = _chunk_major(*operands, chunk)
    dys = _chunk_major(dy.astype(x.dtype), *operands[1:], chunk)[0]

    def step(dS, at):
        """``dS``: the cotangent of the state this chunk ENDS with."""
        x, S, dy = at
        _, vjp = jax.vjp(_chunk, S, x)
        return vjp((dS, dy.astype(F32)))

    _, grads = lax.scan(step, jnp.zeros_like(states[0]), (xs, states, dys),
                        reverse=True)
    return tuple(jnp.moveaxis(g, 0, 1).reshape(a.shape).astype(a.dtype)
                 for g, a in zip(grads, operands))


_scan_core.defvjp(_scan_core_fwd, _scan_core_bwd)


# ---------------------------------------------------------------------
# The Pallas TPU kernel pair: a step's states in VMEM.
# ---------------------------------------------------------------------

def _mix(parts, P, axis):
    """One number a head (and token), a slab's heads', side by side as
    the slab's channels lie. ``axis`` 1: ``parts`` = the heads' [L, 1]
    -> [L, len(parts) P], head ``k``'s in lanes ``k P`` and up; ``axis``
    0: the heads' [1, N] -> [len(parts) P, N], head ``k``'s in rows ``k
    P`` and up (a state's)."""
    shape = list(parts[0].shape)
    shape[axis] = len(parts) * P
    out = jnp.broadcast_to(parts[0], shape)
    for k in range(1, len(parts)):
        at = lax.broadcasted_iota(jnp.int32, shape, axis)
        out = jnp.where(at >= k * P, jnp.broadcast_to(parts[k], shape), out)
    return out


def _of_head(x, k, per, P, axis=1):
    """``x`` with everything but head ``k``'s lanes (``axis`` 0: rows)
    of a slab of ``per`` heads zeroed."""
    if per == 1:
        return x
    at = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where((at >= k * P) & (at < (k + 1) * P), x, jnp.zeros_like(x))


def _gates(gamma, dtr):
    """A chunk's gates for ``hb`` heads, from the rows ``gamma``, ``dtr``
    [hb, 1, L] float32 as they cross HBM: the same down the sublanes, the
    chunk's last, the decays [hb, L, L]."""
    L = gamma.shape[-1]
    i, j = _eye(L)
    g = _down(gamma)
    return types.SimpleNamespace(
        g=g, d=_down(dtr), last=gamma[..., L - 1:],
        decay=jnp.exp(jnp.where(i >= j, g - gamma, -jnp.inf)))


def _slab_gates(t, heads, P, N):
    """What a slab of the heads ``heads`` (indices into the step's) reads
    of the gates ``t``, mixed over its lanes (and, ``forget``, its
    state's rows)."""
    lastN = [jnp.broadcast_to(t.last[h], (1, N)) for h in heads]
    return types.SimpleNamespace(
        d=_mix([t.d[h] for h in heads], P, 1),
        eg=_mix([jnp.exp(t.g[h]) for h in heads], P, 1),
        # last [1, 1] down the sublanes, then the exp, then along the
        # lanes in the product: Mosaic folds two broadcasts in a row.
        ekd=_mix([jnp.exp(jnp.broadcast_to(t.last[h], t.g[h].shape)
                          - t.g[h]) for h in heads], P, 1),
        forget=_mix([jnp.exp(r) for r in lastN], P, 0))


def _slabs(hb, P):
    """The step's heads by slabs of 128 lanes (a head's channels where
    they are more): [(lanes, heads)], and the heads a slab."""
    per = min(max(LANES // P, 1), hb)
    return [(slice(s * per * P, (s + 1) * per * P),
             list(range(s * per, (s + 1) * per)))
            for s in range(hb // per)], per


def _fwd_kernel(x_ref, dt_ref, gamma_ref, b_ref, c_ref, y_ref, *rest, P):
    """One grid step: a chunk of ``hb`` heads from the state in
    ``S_ref`` [hb P, N] float32, which lives across the chunk axis (the
    last, sequential one). ``rest`` = (states_ref, S_ref) where the
    states are kept, else (S_ref,)."""
    S_ref = rest[-1]
    n = pl.program_id(2)
    row = pl.ds(n, 1)

    @pl.when(n == 0)
    def _start():
        S_ref[...] = jnp.zeros_like(S_ref)

    if len(rest) == 2:
        rest[0][...] = S_ref[...]
    hb, N = dt_ref.shape[0], b_ref.shape[-1]
    dtype = x_ref.dtype
    t = _gates(gamma_ref[:, row, :], dt_ref[:, row, :])
    Bm, Cm = b_ref[...], c_ref[...]
    cb = _mm("ik,jk->ij", Cm, Bm)
    p = (cb * t.decay).astype(dtype)                       # [hb, L, L]
    slabs, per = _slabs(hb, P)
    for lanes, heads in slabs:
        s = _slab_gates(t, heads, P, N)
        xf = x_ref[:, lanes].astype(F32)
        xd = (xf * s.d).astype(dtype)
        S = S_ref[lanes, :]
        y = s.eg * _mm("ik,pk->ip", Cm, S.astype(dtype))
        for k, h in enumerate(heads):
            y = y + _of_head(_mm("ij,jp->ip", p[h], xd), k, per, P)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        xdk = (xf * s.d * s.ekd).astype(dtype)
        S_ref[lanes, :] = s.forget * S + _mm("jp,jk->pk", xdk, Bm)


def _bwd_kernel(x_ref, dt_ref, gamma_ref, b_ref, c_ref, states_ref, dy_ref,
                dx_ref, ddt_ref, dgamma_ref, db_ref, dc_ref, dS_ref, *, P):
    """The reverse pass: grid step ``n`` holds chunk ``T / L - 1 - n``
    (the index maps count down); ``dS_ref`` is the cotangent of the state
    that chunk ends with. The forward's lines, then their transposes::

        y  = p xd + eg * (C S^T)        p = (C B^T) * decay,  xd = dt x
        S' = forget * S + xdk^T B       xdk = dt x * exp(gamma_L - gamma)
    """
    n = pl.program_id(2)
    row = pl.ds(pl.num_programs(2) - 1 - n, 1)

    @pl.when(n == 0)
    def _start():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    hb, N = dt_ref.shape[0], b_ref.shape[-1]
    L = x_ref.shape[0]
    dtype = x_ref.dtype
    t = _gates(gamma_ref[:, row, :], dt_ref[:, row, :])
    Bm, Cm = b_ref[...], c_ref[...]
    cb = _mm("ik,jk->ij", Cm, Bm)
    p = (cb * t.decay).astype(dtype)
    slabs, per = _slabs(hb, P)
    dps, dgs, dds = [], [None] * hb, [None] * hb
    dB = jnp.zeros((L, N), F32)
    dC = jnp.zeros((L, N), F32)
    tail = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    for lanes, heads in slabs:
        s = _slab_gates(t, heads, P, N)
        xf = x_ref[:, lanes].astype(F32)
        dy = dy_ref[:, lanes]
        xd = (xf * s.d).astype(dtype)
        xdk = (xf * s.d * s.ekd).astype(dtype)
        S, dS = states_ref[lanes, :], dS_ref[lanes, :]
        Sd, dSd = S.astype(dtype), dS.astype(dtype)
        # through y
        r = _mm("ik,pk->ip", Cm, Sd)                      # C S^T
        dyf = dy.astype(F32)
        dyg = (dyf * s.eg).astype(dtype)
        dC = dC + _mm("ip,pk->ik", dyg, Sd)
        dxd = jnp.zeros(xf.shape, F32)
        for k, h in enumerate(heads):
            dxd = dxd + _of_head(_mm("ij,ip->jp", p[h], dy), k, per, P)
            dps.append(_mm("ip,jp->ij", _of_head(dy, k, per, P), xd))
        # through S'
        dxdk = _mm("jk,pk->jp", Bm, dSd)
        dB = dB + _mm("jp,pk->jk", xdk, dSd)
        dS_ref[lanes, :] = s.forget * dS + _mm("ip,ik->pk", dyg, Cm)
        # the elementwise chains
        dx_ref[:, lanes] = ((dxd + dxdk * s.ekd) * s.d).astype(dx_ref.dtype)
        inter = dyf * r * s.eg
        kept = dxdk * xf * s.ekd            # times d: d gamma_L - gamma
        sds = S * dS
        for k, h in enumerate(heads):
            rk = t.d[h] * _rowsum(_of_head(kept, k, per, P))
            dlast = jnp.sum(rk, axis=0, keepdims=True) + jnp.exp(t.last[h]) \
                * jnp.sum(_rowsum(_of_head(sds, k, per, P, axis=0)), axis=0,
                          keepdims=True)
            dgs[h] = _rowsum(_of_head(inter, k, per, P)) - rk \
                + jnp.where(tail, jnp.broadcast_to(dlast, (L, 1)), 0.0)
            dds[h] = _rowsum(_of_head(dxd * xf + kept, k, per, P))
    # through p = (C B^T) * decay
    dp = jnp.stack(dps) * t.decay                          # [hb, L, L]
    dcb = jnp.sum(dp, axis=0).astype(dtype)
    db_ref[...] = (dB + _mm("ij,ik->jk", dcb, Cm)).astype(db_ref.dtype)
    dc_ref[...] = (dC + _mm("ij,jk->ik", dcb, Bm)).astype(dc_ref.dtype)
    m = dp * cb
    dgamma_ref[:, row, :] = _along(jnp.stack(dgs) + _rowsum(m)) \
        - jnp.sum(m, axis=-2, keepdims=True)
    ddt_ref[:, row, :] = _along(jnp.stack(dds))


def _call(name, kernel, operands, grid, in_specs, out_specs, out_shape,
          scratch, interpret):
    """``metadata`` is the name a device trace shows of the call. Batch
    and head blocks in any order, a sequence's chunks one after another;
    the scratch is the state (or its cotangent) of a step's heads."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        scratch_shapes=[pltpu.VMEM(scratch, F32)],
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(*operands)


def _specs(shape, N, hb, P, G, L, at):
    """(grid, the block specs) of a chunk of ``hb`` heads a step,
    ``at(n)`` the chunk grid step ``n`` takes, over ``x`` [B, T, H P] =
    ``shape`` and ``Bm`` [B, T, G N], a step's heads inside one group:
    ``wide`` a chunk of the heads' channels, ``rows`` the heads' gates of
    a whole sequence [hb, T / L, L], ``token`` the group's ``B`` / ``C``
    of a chunk, ``kept`` the heads' state of one chunk of [T / L, B, H P,
    N], ``part`` a step's share of ``dB`` / ``dC`` in [B, T, H / hb N]."""
    B, T, HP = shape
    steps = HP // P // hb                # head blocks; steps / G a group
    wide = pl.BlockSpec((None, L, hb * P), lambda b, h, n: (b, at(n), h))
    rows = pl.BlockSpec((None, hb, T // L, L), lambda b, h, n: (b, h, 0, 0))
    token = pl.BlockSpec((None, L, N),
                         lambda b, h, n: (b, at(n), h * G // steps))
    kept = pl.BlockSpec((None, None, hb * P, N),
                        lambda b, h, n: (at(n), b, h, 0))
    part = pl.BlockSpec((None, L, N), lambda b, h, n: (b, at(n), h))
    return (B, steps, T // L), wide, rows, token, kept, part


@functools.partial(jax.jit, static_argnames=("keep", "hb", "P", "G", "L",
                                             "interpret", "where"))
def _kernel_fwd(x, dt, gamma, Bm, Cm, *, keep, hb, P, G, L, interpret,
                where="hvd.ssd.core"):
    """``x`` [B, T, H P]; ``dt``, ``gamma`` [B, H, T / L, L] float32;
    ``Bm``, ``Cm`` [B, T, G N] -> [``y`` like ``x``], and with ``keep``
    the state every chunk started from, [T / L, B, H P, N] float32.
    Jitted on its own: every site that enters it with these shapes calls
    ONE lowered function (``gated_delta_rule._kernel_fwd``), whose
    instructions carry the scope ``where`` (the mixer's: a Mamba-2
    layer's ``hvd.ssd.core``, a lightning layer's its own)."""
    with scope(where):
        B, T, HP = x.shape
        N = Bm.shape[-1] // G
        grid, wide, rows, token, kept, _ = _specs(
            x.shape, N, hb, P, G, L, lambda n: n)
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)] + [
            jax.ShapeDtypeStruct((T // L, B, HP, N), F32)] * keep
        return _call("hvd_ssd_fwd", functools.partial(_fwd_kernel, P=P),
                     (x, dt, gamma, Bm, Cm), grid,
                     [wide, rows, rows, token, token],
                     [wide] + [kept] * keep, out_shape, (hb * P, N),
                     interpret)


@functools.partial(jax.jit, static_argnames=("hb", "P", "G", "L",
                                             "interpret", "where"))
def _kernel_bwd(x, dt, gamma, Bm, Cm, states, dy, *, hb, P, G, L,
                interpret, where="hvd.ssd.core"):
    """-> (dx, ddt, dgamma, dBm, dCm) in their operands' shapes and
    dtypes; a group's ``dBm`` / ``dCm`` summed over its steps here."""
    with scope(where):
        B, T, HP = x.shape
        steps = HP // P // hb
        N = Bm.shape[-1] // G
        grid, wide, rows, token, kept, part = _specs(
            x.shape, N, hb, P, G, L, lambda n: T // L - 1 - n)
        # a step that holds a whole group (Lightning Attention: a group a
        # head) writes the group's dB / dC as they are; a group of
        # several steps sums its float32 parts below
        parts = jax.ShapeDtypeStruct(
            (B, T, steps * N), Bm.dtype if steps == G else F32)
        dx, ddt, dgamma, dB, dC = _call(
            "hvd_ssd_bwd", functools.partial(_bwd_kernel, P=P),
            (x, dt, gamma, Bm, Cm, states, dy.astype(x.dtype)), grid,
            [wide, rows, rows, token, token, kept, wide],
            [wide, rows, rows, part, part],
            [jax.ShapeDtypeStruct(x.shape, x.dtype),
             jax.ShapeDtypeStruct(dt.shape, F32),
             jax.ShapeDtypeStruct(dt.shape, F32), parts, parts],
            (hb * P, N), interpret)

        def group(d):
            if steps == G:
                return d
            return d.reshape(B, T, G, steps // G, N).sum(3).reshape(
                Bm.shape).astype(Bm.dtype)

        return dx, ddt, dgamma, group(dB), group(dC)


def _step(x, Bm, L, where="hvd.ssd.core"):
    """What a grid step takes of these operands [B, T, H, P] / [B, T, G,
    N], and how it runs: the kernels' static arguments."""
    H, G = x.shape[2], Bm.shape[2]
    return {"hb": _pick_block(H // G, HEADS_A_STEP), "P": x.shape[3],
            "G": G, "L": L, "interpret": _INTERPRET, "where": where}


def _rows(gate, L):
    """A gate [B, T, H] <-> [B, H, T / L, L]: a chunk's a lane-dense row
    of a block that holds a sequence's."""
    B, T, H = gate.shape
    return jnp.moveaxis(gate, 2, 1).reshape(B, H, T // L, L)


def _tokens(rows):
    """``_rows``' inverse."""
    B, H = rows.shape[:2]
    return jnp.moveaxis(rows.reshape(B, H, -1), 1, 2)


def _flat(x, dt, gamma, Bm, Cm, L):
    B, T = x.shape[:2]
    return (x.reshape(B, T, -1), _rows(dt, L), _rows(gamma, L),
            Bm.reshape(B, T, -1), Cm.reshape(B, T, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_core(x, dt, gamma, Bm, Cm, how):
    """``_scan_core`` on the kernel pair: same operands, same ``y``.
    ``how``: :func:`_step`'s word for these operands, its items."""
    how = dict(how)
    return _kernel_fwd(*_flat(x, dt, gamma, Bm, Cm, how["L"]), keep=False,
                       **how)[0].reshape(x.shape)


def _kernel_core_fwd(x, dt, gamma, Bm, Cm, how):
    how = dict(how)
    flat = _flat(x, dt, gamma, Bm, Cm, how["L"])
    y, states = _kernel_fwd(*flat, keep=True, **how)
    return y.reshape(x.shape), (x, Bm, flat, states)


def _kernel_core_bwd(how, res, dy):
    x, Bm, flat, states = res
    dx, ddt, dgamma, dB, dC = _kernel_bwd(
        *flat, states, dy.reshape(flat[0].shape), **dict(how))
    return (dx.reshape(x.shape), _tokens(ddt), _tokens(dgamma),
            dB.reshape(Bm.shape), dC.reshape(Bm.shape))


_kernel_core.defvjp(_kernel_core_fwd, _kernel_core_bwd)


def ssd(x, dt, A, Bm, Cm, D, chunk=CHUNK, where="hvd.ssd.core"):
    """``y`` [B, T, H, P], in ``x``'s dtype, of the recurrence above for
    ``x`` [B, T, H, P] (the convolved, activated input by heads), the
    step sizes ``dt`` [B, T, H] (positive: after the softplus), the
    decay rates ``A`` [H] (negative), a token's input and output maps
    ``Bm``, ``Cm`` [B, T, G, N], one a group of ``H / G`` consecutive
    heads, and the skip ``D`` [H] (None: no skip, and no pass over ``x``
    for it). ``T`` is a multiple of ``chunk``. Differentiable in all six.
    Lightning Attention's layer (arXiv:2401.04658) is this recurrence
    with ``x = v``, ``Bm = k``, ``Cm = q / sqrt(d)``, ``dt = 1``, ``A`` =
    minus the head's decay rate, no ``D``, a group a head and ``P = N``
    = the head's width: a step of the kernels then holds one head, whose
    channels fill a 128-lane slab alone. ``where``: the device scope the
    kernels' instructions carry."""
    B, T, H, P = x.shape
    G = Bm.shape[2]
    if T % chunk:
        raise ValueError(
            f"ssd works in chunks of {chunk} tokens: a sequence of {T} is "
            "no multiple (pad it; a padded token with dt = 0 neither "
            "decays nor writes)")
    if H % G:
        raise ValueError(f"{H} heads are no multiple of {G} groups: each "
                         "group's B and C serve a whole number of heads")
    dt, A = dt.astype(F32), A.astype(F32)
    gamma = jnp.cumsum((dt * A).reshape(B, T // chunk, chunk, H),
                       axis=2).reshape(B, T, H)
    operands = (x, dt, gamma, Bm.astype(x.dtype), Cm.astype(x.dtype))
    if use_pallas("ssd", operands, _INTERPRET):
        y = _kernel_core(*operands,
                         tuple(_step(x, Bm, chunk, where).items()))
    else:
        y = _scan_core(*operands, chunk)
    if D is None:
        return y
    return (y.astype(F32) + D.astype(F32)[:, None] * x.astype(F32)
            ).astype(x.dtype)
