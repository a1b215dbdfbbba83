"""The gated delta rule in chunked form, forward and backward (Gated
DeltaNet: Yang et al., arXiv:2412.06464, section 3).

The rule, a head at a time, with a state ``S`` in ``R^{dk x dv}``,
``S_0 = 0``, for each token ``t``::

    S <- exp(g_t) S               (the gate: g_t <= 0)
    r_t = v_t - S^T k_t           (what the state does not yet say of v_t)
    S <- S + k_t (beta_t r_t)^T   (the delta rule's rank-one write)
    o_t = S^T q_t

Token by token that is 8192 dependent steps of vector work. In chunks of
``C`` = 64 tokens it is matmuls. With ``gamma_i = sum_{j<=i} g_j`` inside
a chunk and ``S`` the state the chunk starts from, the rows the chunk
writes, ``V' = [beta_t r_t]``, solve a unit lower-triangular system (the
WY form)::

    A  = strictly-lower(diag(beta) (K K^T * exp(gamma_i - gamma_j)))
    T  = (I + A)^-1
    U  = T diag(beta) V
    W  = T diag(beta) (K * exp(gamma))
    V' = U - W S
    O  = (Q * exp(gamma)) S + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

How the operands lie: TOKEN-MAJOR, as the projections' matmuls and
``ops/gdn_chain.py``'s kernels leave and take them: ``q``, ``k`` [B, T,
hk dk], ``v`` and ``o`` [B, T, hv dv], the tokens on the sublanes and a
token's heads side by side on the lanes. (Read as ``[B, T, H, d]`` the
same bytes are tiled with the HEADS on the sublanes: a reshape between
the two is a copy of the whole array on a TPU, and a chunk-major ``[B,
N, H, C, d]`` a transposing copy more.) ``[B, T, H d]`` viewed as ``[B,
N, C, H d]`` is free. A key head serves ``r = hv / hk`` value heads in a
row (value head ``h`` reads key head ``h // r``) and is INDEXED, never
repeated in HBM.

Nothing but ``S`` crosses a chunk, so the work falls into three parts:

1. what leads to ``T``: the decays ``gamma`` and ``exp(gamma_i -
   gamma_j)``, ``K K^T``, ``A`` and the solve, for ALL chunks at once,
   by ordinary batched operations that autodiff differentiates. ``K
   K^T`` is formed a KEY head (``k`` copied head-major ``[B, N, hk, C,
   dk]`` for it, the one copy of an operand that is left, and that
   product's share of ``dk`` copied back); ``A``, the solve and ``T``
   are a value head's (``beta`` and ``gamma`` are). ``T`` is born here,
   in XLA (``lax.linalg.triangular_solve``), rounded to the compute
   dtype, on every carrier, ``[B, N, hv, C, C]``;
2. the WY factors, each a function of a chunk's own ``q``, ``k``, ``v``,
   ``gamma``, ``beta`` and ``T``::

       qg = Q * exp(gamma)                 kd = K * exp(gamma_C - gamma)
       p  = tril(Q K^T * exp(gamma_i - gamma_j))
       bv = diag(beta) V                   bk = diag(beta) (K * exp(gamma))
       u  = T bv                           w  = T bk

3. the three lines that read ``S``, chunk after chunk::

       new = u - w S;   o = qg S + p new;   S <- exp(gamma_C) S + kd^T new

One algorithm, two carriers for parts 2 and 3; which one runs is read
off the operands (``ops/_platform.py``, as the flash kernels decide it),
never off an option:

- operands on a TPU: a Pallas kernel pair that takes a chunk's RAW
  operands and ``T`` and forms the factors in VMEM, a grid step at a
  time: ``qg``, ``p``, ``u``, ``w``, ``kd`` (134 MB each at B2 T8192 H32
  d128, ``p`` 67) and their five cotangents are born and die there, in
  the forward, the forward under remat and the backward; none crosses
  HBM, none is a residual. ``hvd_gdn_rule_fwd`` walks a grid ``(B, hv /
  hb, T / C)`` whose last axis, the chunks, is sequential; the state of
  ``hb`` heads lives in a float32 VMEM scratch for the whole sequence
  and never crosses HBM (but where the states are kept).
  ``hvd_gdn_rule_bwd`` walks the same grid from the last chunk to the
  first with the state's cotangent in the scratch: it forms the factors
  again, runs the scan's reverse step, and takes the factors'
  cotangents on to the operands' in the same step (the equations
  below). One set of kernel bodies behind TWO block layouts, chosen by
  the operands' shapes (``_token_major_step``):

  - token-major: the block of ``q``, ``k``, ``v``, ``o``, ``do``,
    ``dq``, ``dk``, ``dv`` is ``(C, hb d)`` of ``[B, T, H d]`` at ``(b,
    chunk, h)``, a chunk's rows of a step's heads side by side. Inside,
    a head is a lane slice at a multiple of ``d`` (``_heads``), a key
    head's taken once for each of the ``r`` value heads it serves;
    ``dq`` and ``dk`` are summed over those ``r`` in float32 before the
    one rounding (``_put``). No copy of ``q``, ``v``, ``o`` or their
    cotangents is left in the program. ``hb`` is a divisor of the heads
    that gives whole lane tiles in both strips (``(hb / r) dk`` and
    ``hb dv`` multiples of 128: eight heads at 128 wide), every slice
    then on a tile's edge; or, widths with no such divisor (30 heads 96
    and 192 wide: heads of 96 want steps of four), ALL the heads, a
    block as wide as the array being legal whatever its lanes: a head
    is then a lane window wherever it falls, the step's state is the
    whole layer's (2.8 MiB padded) and the call asks for its VMEM by
    name. On the chip, the rule alone forward and backward at B2 T8192:
    26.04 -> 18.81 ms at 32 heads of 128 on 16 key heads, 30.17 -> 21.17
    at 30 of 96 / 192 (PERF.md, PR 62);
  - chunk-major, where the whole width's state would not fit either
    (``_WHOLE_WIDTH_STATE``): ``q``, ``k``, ``v`` copied to ``[B, N, H,
    C, d]`` (a key head repeated by that copy), ``o`` and the
    cotangents copied back, a block the ``hb`` heads of a chunk.

  Either way ``T`` is read where part 1 leaves it, ``[B, N, hv, C, C]``
  (an index map takes block ``(b, n, h)`` as readily as ``(n, b, h)``);
  only the kept states are chunk-major, ``[N, B, hv, dk, dv]``.
  The gates cross HBM lane-dense, ``[B, H, N, C]`` float32, a
  sequence's block staying in VMEM and a chunk's row ``[hb, 1, C]``
  read from it; a gate scales ROWS of a ``[C, d]`` tile, so the kernel
  turns the row down the sublanes first (``_down``: a select against
  the identity and a lane sum, exact) and the row sums that are the
  gates' gradients back (``_along``). The names are the calls'
  ``kernel_metadata``, what a device trace shows of them. Each kernel
  sits behind ONE jitted function: every layer and phase of a program
  calls one lowered copy (a ``pallas_call`` is lowered to Mosaic
  wherever it is traced, compile cache or not, and a set-up pays for
  each): the forward twice (keeping the states, and not), the backward
  once;
- elsewhere: ``_scan_rule``, the factors by batched operations that
  autodiff differentiates, each through HBM, and ``_scan_state``, a
  ``lax.scan`` over chunk-major operands under a ``custom_vjp``, behind
  the chunk-major layout's copies (the CPU's path, and the tests'
  reference for the kernels, which run there in interpret mode under
  ``_INTERPRET``).

Either way the forward keeps the state each chunk STARTED from (T/C x
[B, H, dk, dv] float32: 537 MB at B2 T8192 H32 d128, alive for one
layer's backward under the layer's remat) and the backward is one
reverse pass that recomputes ``new`` and carries the state's cotangent.
Kept and not recomputed: the states are what a recomputation would have
to run the whole forward pass again for, and one layer's are a thirtieth
of the chip.

The kernels' backward, a chunk from ``do`` and ``dS``, the cotangent of
the state the chunk ENDS with (``r()`` rounds to the compute dtype where
the scan hands a factor's cotangent to autodiff; products of two
operands are MXU matmuls, ``*`` is elementwise, ``rows()`` sums along a
token's row, ``cols()`` down a column)::

    the scan's step (``_scan_state_bwd``):
    du   = r(p^T do + kd dS)             (= dnew)
    dqg  = r(do S^T)    dp = r(do new^T)
    dw   = r(-du S^T)   dkd = r(new dS^T)    ddc = sum(S * dS)
    dS  <- exp(gamma_C) dS + qg^T do - w^T du

    the factors' own:
    dT   = du bv^T + dw bk^T             (out: autodiff takes it through
                                          the solve, A, K K^T, the mask)
    dbv  = r(T^T du)    dbk = r(T^T dw)
    dpd  = dp * exp(gamma_i - gamma_j)   (0 above the diagonal)
    dq   = dqg * exp(gamma) + r(dpd) K
    dk   = r(dpd)^T Q + dkd * exp(gamma_C - gamma)
           + dbk * beta * exp(gamma)
    dv   = dbv * beta
    dbeta  = rows(dbv * V) + exp(gamma) * rows(dbk * K)
    dgamma = exp(gamma) * rows(dqg * Q) + beta * exp(gamma) * rows(dbk * K)
             - exp(gamma_C - gamma) * rows(dkd * K)
             + rows(dpd * Q K^T) - cols(dpd * Q K^T)
    dgamma_C += ddc * exp(gamma_C)
                + sum(exp(gamma_C - gamma) * rows(dkd * K))

``dk`` has a second share (``K K^T``'s, a key head's) and ``dbeta``,
``dgamma`` a second each (``A``'s): autodiff's, through part 1, added
outside.

The seam left for the inverse: ``T`` enters the kernels as an operand
and ``dT`` leaves as a result. A kernel that forms ``T`` itself (a block
inverse on the MXU from ``K K^T``, which it can form too) changes where
those two are born and nothing else here.

Precision: the decays (``gamma``, every ``exp``, all of non-positive
arguments, so none overflows), the inverse (forward substitution, not a
Neumann series: stable whatever the keys), the state and every
accumulation are float32; the matmuls take operands in the compute dtype
(``q``'s; the inverse, the factors and the state rounded to it as they
enter one) and accumulate in float32. Both carriers and both block
layouts round at the same places: on the same operands the two layouts
give ``o`` the same to the last bit, and the scan too wherever its
matmuls add a row's products in the kernels' order.
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
from horovod_tpu.utils.spans import scope

F32 = jnp.float32
CHUNK = 64
# Tests flip this to run the kernel pair in pallas interpret mode on the
# CPU (as ``flash_attention._INTERPRET``).
_INTERPRET = False
# What one grid step of a kernel takes: a chunk of so many heads (a
# batched matmul), or of the largest divisor of H under it.
HEADS_A_STEP = 8
_LANES = 128         # lanes of a tile
_SUBLANES = 8        # rows of a float32 tile
# Token-major blocks of the WHOLE width (heads of no lane tile's width):
# taken where the float32 state of all the heads, its tiles padded, is
# under so many bytes (30 heads of 96 x 192 hold 2.8 MiB, and the
# backward then wants between 32 and 48 MiB of VMEM where a kernel is
# given 16 unasked), and the VMEM such a step asks for by name.
_WHOLE_WIDTH_STATE = 4 * 2 ** 20
_WHOLE_WIDTH_VMEM = 100 * 2 ** 20


def _mm(spec, a, b):
    """An einsum of compute-dtype operands accumulated in float32."""
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _steps(xs):
    """The scan's per-chunk operands, ``dc`` broadcast over a state."""
    qg, p, u, w, kd, dc = xs
    return qg, p, u, w, kd, dc[..., None, None]


def _chunk(S, x):
    """One chunk from the state ``S`` [B, H, dk, dv] it starts with ->
    (the state it ends with, its outputs [B, H, C, dv])."""
    qg, p, u, w, kd, dc = x
    dt = qg.dtype
    new = u.astype(F32) - _mm("bhck,bhkv->bhcv", w, S.astype(dt))
    o = _mm("bhck,bhkv->bhcv", qg, S.astype(dt)) \
        + _mm("bhcj,bhjv->bhcv", p, new.astype(dt))
    return dc * S + _mm("bhck,bhcv->bhkv", kd, new.astype(dt)), o


def _zero_state(qg, u):
    return jnp.zeros(qg.shape[1:3] + (qg.shape[-1], u.shape[-1]), F32)


@jax.custom_vjp
def _scan_state(qg, p, u, w, kd, dc):
    """The state-carrying pass. Chunk-major operands, ``N`` chunks of
    ``C`` tokens: ``qg`` = ``Q * exp(gamma)``, ``w``, ``kd`` = ``K *
    exp(gamma_C - gamma)`` [N, B, H, C, dk]; ``u`` [N, B, H, C, dv];
    ``p`` the decayed causal in-chunk scores [N, B, H, C, C]; ``dc`` =
    ``exp(gamma_C)`` [N, B, H] float32 -> ``o`` [N, B, H, C, dv] in
    ``u``'s dtype."""
    _, o = lax.scan(_chunk, _zero_state(qg, u),
                    _steps((qg, p, u, w, kd, dc)))
    return o.astype(u.dtype)


def _scan_state_fwd(qg, p, u, w, kd, dc):
    def step(S, x):
        S_next, o = _chunk(S, x)
        return S_next, (o, S)

    _, (o, states) = lax.scan(step, _zero_state(qg, u),
                              _steps((qg, p, u, w, kd, dc)))
    return o.astype(u.dtype), (qg, p, u, w, kd, dc, states)


def _scan_state_bwd(res, do):
    qg, p, u, w, kd, dc, states = res
    dt = qg.dtype

    def step(dS, x):
        """``dS``: the cotangent of the state this chunk ENDS with."""
        (qg, p, u, w, kd, dc), S, do = x
        Sd, dSd = S.astype(dt), dS.astype(dt)
        new = (u.astype(F32) - _mm("bhck,bhkv->bhcv", w, Sd)).astype(dt)
        dnew = (_mm("bhcj,bhcv->bhjv", p, do)
                + _mm("bhck,bhkv->bhcv", kd, dSd)).astype(dt)
        grads = (_mm("bhcv,bhkv->bhck", do, Sd),            # qg
                 _mm("bhcv,bhjv->bhcj", do, new),           # p
                 dnew,                                      # u
                 -_mm("bhcv,bhkv->bhck", dnew, Sd),         # w
                 _mm("bhcv,bhkv->bhck", new, dSd),          # kd
                 jnp.sum(S * dS, (-2, -1)))                 # dc
        dS = dc * dS + _mm("bhck,bhcv->bhkv", qg, do) \
            - _mm("bhck,bhcv->bhkv", w, dnew)
        return dS, grads

    _, grads = lax.scan(
        step, jnp.zeros_like(states[0]),
        (_steps((qg, p, u, w, kd, dc)), states, do.astype(dt)),
        reverse=True)
    return tuple(g.astype(x.dtype)
                 for g, x in zip(grads, (qg, p, u, w, kd, dc)))


_scan_state.defvjp(_scan_state_fwd, _scan_state_bwd)


# ---------------------------------------------------------------------
# The rule as a Pallas TPU kernel pair: the factors and the state in VMEM.
# ---------------------------------------------------------------------

def _eye(C):
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    return i, lax.broadcasted_iota(jnp.int32, (C, C), 1)


def _down(row):
    """A gate a token [hb, 1, C], along the lanes as it crosses HBM ->
    [hb, C, 1], down the sublanes, where it scales the rows of a ``[C,
    d]`` tile. Exactly: a sum of one entry and zeros."""
    i, j = _eye(row.shape[-1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=-1, keepdims=True)


def _along(col):
    """``_down``'s inverse: [hb, C, 1] -> [hb, 1, C]."""
    i, j = _eye(col.shape[-2])
    return jnp.sum(jnp.where(i == j, col, 0.0), axis=-2, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _forget(last, S):
    """``exp(gamma_C) S`` for ``last`` = ``gamma_C`` [hb, 1, 1]. Mosaic
    broadcasts along the lanes or down the sublanes, not both at once
    (and folds two in a row into one): along the lanes, the ``exp``,
    and the product is the second."""
    return jnp.exp(jnp.broadcast_to(last, last.shape[:-1] + S.shape[-1:])) * S


def _factors(q, k, v, gamma, beta, inv):
    """The WY factors of a chunk of ``hb`` heads, ``gated_delta_rule``'s
    lines and roundings: ``q``, ``k`` [hb, C, dk], ``v`` [hb, C, dv],
    ``inv`` [hb, C, C] in the compute dtype, the gates ``gamma``,
    ``beta`` [hb, 1, C] float32 -> what the three lines that read the
    state take, and what the backward takes besides."""
    dt, C = q.dtype, q.shape[-2]
    i, j = _eye(C)
    g, b = _down(gamma), _down(beta)
    last = gamma[..., C - 1:]                               # [hb, 1, 1]
    eg, ekd = jnp.exp(g), jnp.exp(last - g)
    decay = jnp.exp(jnp.where(i >= j, g - gamma, -jnp.inf))
    qk = _mm("hck,hjk->hcj", q, k)
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    beg = b * eg
    bv, bk = (b * vf).astype(dt), (beg * kf).astype(dt)
    return types.SimpleNamespace(
        qg=(qf * eg).astype(dt), p=(qk * decay).astype(dt),
        u=_mm("hcj,hjv->hcv", inv, bv).astype(dt),
        w=_mm("hcj,hjk->hck", inv, bk).astype(dt),
        kd=(kf * ekd).astype(dt), last=last, bv=bv, bk=bk, b=b, eg=eg,
        beg=beg, ekd=ekd, decay=decay, qk=qk, qf=qf, kf=kf, vf=vf)


def _chunk_fwd(q, k, v, gamma, beta, inv, S):
    """A chunk of ``hb`` heads from the state ``S`` [hb, dk, dv] float32
    it starts with -> (``o`` [hb, C, dv] float32, the state it ends
    with): ``_chunk``'s lines on factors that never leave VMEM."""
    f = _factors(q, k, v, gamma, beta, inv)
    Sd = S.astype(q.dtype)
    new = (f.u.astype(F32) - _mm("hck,hkv->hcv", f.w, Sd)).astype(q.dtype)
    o = _mm("hck,hkv->hcv", f.qg, Sd) + _mm("hcj,hjv->hcv", f.p, new)
    return o, _forget(f.last, S) + _mm("hck,hcv->hkv", f.kd, new)


def _chunk_bwd(q, k, v, gamma, beta, inv, S, dS, do):
    """The chunk's backward, ``dS`` the cotangent of the state it ENDS
    with: the factors again, ``_scan_state_bwd``'s ``step``, then the
    factors' own transposes (the module docstring's equations) ->
    (``dq``, ``dk``, ``dv``, ``dinv`` float32, ``dgamma``, ``dbeta``
    [hb, 1, C], the cotangent of the state it STARTED with)."""
    dt, C = q.dtype, q.shape[-2]
    f = _factors(q, k, v, gamma, beta, inv)
    qg, p, w, kd = f.qg, f.p, f.w, f.kd
    b, eg, beg, ekd, qf, kf = f.b, f.eg, f.beg, f.ekd, f.qf, f.kf
    Sd, dSd = S.astype(dt), dS.astype(dt)
    new = (f.u.astype(F32) - _mm("hck,hkv->hcv", w, Sd)).astype(dt)
    du = (_mm("hcj,hcv->hjv", p, do)
          + _mm("hck,hkv->hcv", kd, dSd)).astype(dt)
    # rounded where the scan hands them to autodiff: the factors' dtype
    dqg = _mm("hcv,hkv->hck", do, Sd).astype(dt).astype(F32)
    dp = _mm("hcv,hjv->hcj", do, new).astype(dt).astype(F32)
    dw = (-_mm("hcv,hkv->hck", du, Sd)).astype(dt)
    dkd = _mm("hcv,hkv->hck", new, dSd).astype(dt).astype(F32)
    ddc = jnp.sum(_rowsum(S * dS), axis=-2, keepdims=True)   # [hb, 1, 1]
    dS = _forget(f.last, dS) + _mm("hck,hcv->hkv", qg, do) \
        - _mm("hck,hcv->hkv", w, du)
    # through u = inv bv and w = inv bk
    dinv = _mm("hcv,hjv->hcj", du, f.bv) \
        + _mm("hck,hjk->hcj", dw, f.bk)
    dbv = _mm("hjc,hjv->hcv", inv, du).astype(dt).astype(F32)
    dbk = _mm("hjc,hjk->hck", inv, dw).astype(dt).astype(F32)
    # through p = q k^T * decay
    dpd = dp * f.decay
    dqk, m = dpd.astype(dt), dpd * f.qk
    dq = dqg * eg + _mm("hcj,hjk->hck", dqk, k)
    dk = _mm("hcj,hck->hjk", dqk, q) + dkd * ekd + dbk * beg
    dv = dbv * b
    # the gates: down the sublanes as the row sums come, then along
    rk, rkd = _rowsum(dbk * kf), ekd * _rowsum(dkd * kf)
    dbeta = _rowsum(dbv * f.vf) + eg * rk
    dgamma = eg * _rowsum(dqg * qf) + beg * rk - rkd + _rowsum(m)
    # gamma_C, the chunk's last: kd's total and dc's
    dlast = ddc * jnp.exp(f.last) + jnp.sum(rkd, axis=-2, keepdims=True)
    _, j = _eye(C)
    dgamma = _along(dgamma) - jnp.sum(m, axis=-2, keepdims=True) \
        + jnp.where(j[:1] == C - 1, dlast, 0.0)
    return dq, dk, dv, dinv, dgamma, _along(dbeta), dS


def _heads(ref, hb, d):
    """A step's ``hb`` heads [hb, C, d] of a block of ``q``, ``k``,
    ``v`` or ``do``. Chunk-major the block is that already. Token-major
    it is ``[C, (hb / r) d]``, a head a lane slice at a multiple of
    ``d``, and a key head is taken once for each of the ``r`` value
    heads it serves (no copy of it in HBM)."""
    if len(ref.shape) == 3:
        return ref[...]
    r = hb * d // ref.shape[-1]
    return jnp.stack([ref[:, i // r * d:(i // r + 1) * d]
                      for i in range(hb)])


def _put(ref, x):
    """``_heads``'s way back for ``x`` [hb, C, d] float32: the block as
    it lies, or a head a lane slice, the ``r`` value heads of a key head
    summed in float32 before the one rounding."""
    if len(ref.shape) == 3:
        ref[...] = x.astype(ref.dtype)
        return
    hb, _, d = x.shape
    r = hb * d // ref.shape[-1]
    for i in range(hb // r):
        ref[:, i * d:(i + 1) * d] = functools.reduce(
            jnp.add, [x[i * r + j] for j in range(r)]).astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, inv_ref, o_ref,
                *rest):
    """One grid step: a chunk of ``hb`` heads from the state in
    ``S_ref`` [hb, dk, dv] float32, which lives across the chunk axis
    (the last, sequential one). ``rest`` = (states_ref, S_ref) where
    the states are kept, else (S_ref,)."""
    S_ref = rest[-1]
    hb, dk, dv = S_ref.shape
    n = pl.program_id(2)
    row = pl.ds(n, 1)

    @pl.when(n == 0)
    def _start():
        S_ref[...] = jnp.zeros_like(S_ref)

    S = S_ref[...]
    if len(rest) == 2:
        rest[0][...] = S
    o, S_ref[...] = _chunk_fwd(
        _heads(q_ref, hb, dk), _heads(k_ref, hb, dk), _heads(v_ref, hb, dv),
        gamma_ref[:, row, :], beta_ref[:, row, :], inv_ref[...], S)
    _put(o_ref, o)


def _bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, inv_ref,
                states_ref, do_ref, dq_ref, dk_ref, dv_ref, dinv_ref,
                dgamma_ref, dbeta_ref, dS_ref):
    """The reverse pass: grid step ``n`` holds chunk ``N - 1 - n`` (the
    index maps count down); ``dS_ref`` is the cotangent of the state
    that chunk ends with."""
    hb, wk, wv = dS_ref.shape
    n = pl.program_id(2)
    row = pl.ds(pl.num_programs(2) - 1 - n, 1)

    @pl.when(n == 0)
    def _start():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    dq, dk, dv, dinv, dgamma, dbeta, dS_ref[...] = _chunk_bwd(
        _heads(q_ref, hb, wk), _heads(k_ref, hb, wk), _heads(v_ref, hb, wv),
        gamma_ref[:, row, :], beta_ref[:, row, :], inv_ref[...],
        states_ref[...], dS_ref[...], _heads(do_ref, hb, wv))
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                   (dinv_ref, dinv)):
        _put(ref, x)
    # a row of a block that stays for the whole sequence
    dgamma_ref[:, row, :] = dgamma
    dbeta_ref[:, row, :] = dbeta


def _call(name, kernel, state, operands, grid, in_specs, out_specs,
          out_shape, interpret):
    """``metadata`` is the name a device trace shows of the call
    (``ops/flash_attention.py:_pallas_dispatch``). Batch and heads in
    any order, a sequence's chunks one after another; the scratch
    ``state`` (hb, dk, dv) is the state (or its cotangent) of a step's
    heads. A step of more heads than ``HEADS_A_STEP`` (the whole width)
    asks for its VMEM by name."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        scratch_shapes=[pltpu.VMEM(state, F32)],
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_WHOLE_WIDTH_VMEM
            if state[0] > HEADS_A_STEP else None),
    )(*operands)


def _specs(q, v, inv, hk, hb, at):
    """(grid, the block specs, the scratch's shape) of ``hb`` value
    heads of a chunk a step, ``at(n)`` the chunk grid step ``n`` takes:
    ``raw``, the six operands' in their order, and ``states`` over the
    kept ``[N, B, H, dk, dv]``. ``q``, ``k`` (of ``hk`` key heads), ``v``
    token-major, ``[B, T, heads d]``: a chunk's rows of a step's heads
    side by side on the lanes, a key head's block index the index of the
    ``hb`` value heads it serves ``H / hk`` of. Chunk-major, ``[B, N, H,
    C, d]``: the heads of a chunk. ``inv`` ``[B, N, H, C, C]``; a gate's
    ``[B, H, N, C]``, a sequence's staying in VMEM."""
    B, N, H, C, _ = inv.shape

    def of_chunks(d):
        return pl.BlockSpec((None, None, hb, C, d),
                            lambda b, h, n: (b, at(n), h, 0, 0))

    def of_tokens(lanes):
        return pl.BlockSpec((None, C, lanes), lambda b, h, n: (b, at(n), h))

    if q.ndim == 5:
        dk, dv = q.shape[-1], v.shape[-1]
        keys, values = of_chunks(dk), of_chunks(dv)
    else:
        dk, dv = q.shape[-1] // hk, v.shape[-1] // H
        keys, values = of_tokens(hb * hk // H * dk), of_tokens(hb * dv)
    rows = pl.BlockSpec((None, hb, N, C), lambda b, h, n: (b, h, 0, 0))
    states = pl.BlockSpec((None, None, hb, dk, dv),
                          lambda b, h, n: (at(n), b, h, 0, 0))
    raw = [keys, keys, values, rows, rows, of_chunks(C)]
    return (B, H // hb, N), raw, states, (hb, dk, dv)


def _rows(gate):
    """A gate [B, N, H, C] <-> [B, H, N, C]: a chunk's a lane-dense row
    of a block that holds a sequence's."""
    return jnp.swapaxes(gate, 1, 2)


@functools.partial(jax.jit,
                   static_argnames=("keep", "hk", "hb", "interpret"))
def _kernel_fwd(q, k, v, gamma, beta, inv, *, keep, hk, hb, interpret):
    """-> [``o``, as ``v`` lies], and with ``keep`` the state every
    chunk started from, [N, B, H, dk, dv] float32. Jitted on its own:
    every site that enters it with these shapes calls ONE lowered
    function, so a program pays a Mosaic lowering a form and not one a
    site (three layers, each forward, forward again under remat, and
    backward). The scope again: a shared body's name stack starts at
    this function; the call site's (its scope, its phase) stands before
    it only where the compiler inlines the call."""
    with scope("hvd.gdn.core"):
        B, N, H = inv.shape[:3]
        grid, raw, states, state = _specs(q, v, inv, hk, hb, lambda n: n)
        out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)] + [
            jax.ShapeDtypeStruct((N, B, H) + state[1:], F32)] * keep
        return _call("hvd_gdn_rule_fwd", _fwd_kernel, state,
                     (q, k, v, _rows(gamma), _rows(beta), inv), grid, raw,
                     [raw[2]] + [states] * keep, out_shape, interpret)


@functools.partial(jax.jit, static_argnames=("hk", "hb", "interpret"))
def _kernel_bwd(q, k, v, gamma, beta, inv, states, do, *, hk, hb,
                interpret):
    """-> the six gradients, in their operands' shapes and dtypes."""
    with scope("hvd.gdn.core"):
        N = inv.shape[1]
        grid, raw, kept, state = _specs(q, v, inv, hk, hb,
                                        lambda n: N - 1 - n)
        gates = (_rows(gamma), _rows(beta))
        *grads, dgamma, dbeta = _call(
            "hvd_gdn_rule_bwd", _bwd_kernel, state,
            (q, k, v, *gates, inv, states, do.astype(q.dtype)), grid,
            raw + [kept, raw[2]], [raw[i] for i in (0, 1, 2, 5, 3, 4)],
            [jax.ShapeDtypeStruct(x.shape, x.dtype)
             for x in (q, k, v, inv) + gates], interpret)
        dq, dk, dv, dinv = grads
        return dq, dk, dv, _rows(dgamma), _rows(dbeta), dinv


def _chunk_major_step(heads):
    """The heads a grid step takes of chunk-major operands."""
    return _pick_block(heads, HEADS_A_STEP)


def _token_major_step(hk, hv, dk, dv):
    """The value heads a grid step takes of token-major operands: the
    largest divisor of the heads under ``HEADS_A_STEP`` that is whole
    key heads' value heads and whose strips, ``(hb / r) dk`` of ``q``
    and ``k`` and ``hb dv`` of ``v``, are whole lane tiles, so that a
    head is a lane slice on a tile's edge. Widths with no such step (96
    and 192 wide on 30 heads: heads of 96 want steps of four): ALL the
    heads, a block as wide as the array being legal whatever its lanes
    (a head is then a lane window wherever it falls), where their state
    is small enough to live in VMEM (``_WHOLE_WIDTH_STATE``); else None,
    the chunk-major blocks."""
    r = hv // hk
    whole = [h for h in range(r, min(hv, HEADS_A_STEP) + 1, r)
             if hv % h == 0 and h // r * dk % _LANES == 0
             and h * dv % _LANES == 0]
    if whole:
        return max(whole)
    state = 4 * hv * -(-dk // _SUBLANES) * _SUBLANES \
        * -(-dv // _LANES) * _LANES
    return hv if state <= _WHOLE_WIDTH_STATE else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_rule(hk, hb, q, k, v, gamma, beta, inv):
    """``_scan_rule`` on the kernel pair, ``hb`` value heads a step:
    same operands, same ``o``, chunk-major, or ``q``, ``k`` (of ``hk``
    key heads), ``v`` and ``o`` token-major."""
    return _kernel_fwd(q, k, v, gamma, beta, inv, keep=False, hk=hk, hb=hb,
                       interpret=_INTERPRET)[0]


def _kernel_rule_fwd(hk, hb, q, k, v, gamma, beta, inv):
    o, states = _kernel_fwd(q, k, v, gamma, beta, inv, keep=True, hk=hk,
                            hb=hb, interpret=_INTERPRET)
    return o, (q, k, v, gamma, beta, inv, states)


def _kernel_rule_bwd(hk, hb, res, do):
    return _kernel_bwd(*res, do, hk=hk, hb=hb, interpret=_INTERPRET)


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _decay(gamma):
    """``exp(gamma_i - gamma_j)`` where i >= j, 0 above the diagonal:
    ``gamma`` [..., C] -> [..., C, C]."""
    C = gamma.shape[-1]
    i, j = lax.iota(jnp.int32, C)[:, None], lax.iota(jnp.int32, C)
    return jnp.exp(jnp.where(i >= j, gamma[..., :, None]
                             - gamma[..., None, :], -jnp.inf))


def _scan_rule(q, k, v, gamma, beta, inv, decay):
    """The factors by batched operations that autodiff differentiates,
    all chunks at once and each through HBM, then the scan, which wants
    them chunk-major."""
    dt = q.dtype
    bv = (beta[..., None] * v.astype(F32)).astype(dt)
    bk = (beta[..., None] * jnp.exp(gamma)[..., None]
          * k.astype(F32)).astype(dt)
    last = gamma[..., -1]
    operands = (
        (q.astype(F32) * jnp.exp(gamma)[..., None]).astype(dt),       # qg
        (_mm("bnhck,bnhjk->bnhcj", q, k) * decay).astype(dt),         # p
        _mm("bnhcj,bnhjv->bnhcv", inv, bv).astype(dt),                # u
        _mm("bnhcj,bnhjk->bnhck", inv, bk).astype(dt),                # w
        (k.astype(F32)
         * jnp.exp(last[..., None] - gamma)[..., None]).astype(dt),   # kd
        jnp.exp(last))                                                # dc
    return jnp.moveaxis(
        _scan_state(*(jnp.moveaxis(x, 1, 0) for x in operands)), 0, 1)


def _rule_of_chunks(q, k, v, gamma, beta, inv, decay):
    """Everything downstream of the inverse, on chunk-major operands
    ``[B, N, H, C, ...]`` (the gates ``[B, N, H, C]`` float32) -> ``o``
    [B, N, H, C, dv]. Operands on a TPU: the kernel pair, which forms
    its own decays. Elsewhere the factors in XLA and the scan."""
    operands = (q, k, v, gamma, beta, inv)
    if use_pallas("gated_delta_rule", operands, _INTERPRET):
        H = q.shape[2]
        return _kernel_rule(H, _chunk_major_step(H), *operands)
    return _scan_rule(*operands, decay)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, key_heads=None):
    """``o`` [B, T, hv dv] of the rule above for ``q``, ``k`` [B, T, hk
    dk] (as they enter the rule: normalised, ``q`` scaled) of
    ``key_heads`` key heads (a value head's own where None), ``v`` [B,
    T, hv dv], token-major as the chain round the rule leaves and takes
    them, the log-decays ``g`` <= 0 and the write strengths ``beta`` [B,
    T, hv] (read as float32). ``T`` is a multiple of ``chunk``.
    Differentiable in all five. A key head serves ``hv / hk`` value
    heads in a row and is never repeated in HBM where the kernels read
    token-major blocks. Operands by heads, ``[B, T, H, d]`` (``o``
    likewise): the same bytes, read as token-major."""
    if q.ndim == 4:
        B, T, hk, _ = q.shape
        o = gated_delta_rule(*(x.reshape(B, T, -1) for x in (q, k, v)),
                             g, beta, chunk, hk)
        return o.reshape(v.shape)
    B, T, _ = q.shape
    hv = g.shape[-1]
    hk = key_heads or hv
    dk, dv, r = q.shape[-1] // hk, v.shape[-1] // hv, hv // hk
    if T % chunk:
        raise ValueError(
            f"gated_delta_rule works in chunks of {chunk} tokens: a "
            f"sequence of {T} is no multiple (pad it; a padded token "
            "with beta = 0 and g = 0 writes and forgets nothing)")
    N, dt = T // chunk, q.dtype

    def chunked(x, *heads):
        """[B, T, heads..] -> [B, N, heads[0], C, ..]: a copy"""
        return jnp.moveaxis(x.reshape(B, N, chunk, *heads), 2, 3)

    g, beta = (chunked(x.astype(F32), hv) for x in (g, beta))
    gamma = jnp.cumsum(g, -1)                                # [B,N,hv,C]
    decay = _decay(gamma)
    i, j = lax.iota(jnp.int32, chunk)[:, None], lax.iota(jnp.int32, chunk)
    # K K^T a KEY head, read by the r value heads it serves: a broadcast
    # inside the product with their gates, and the systems solved as
    # [B, N, hk, r, C, C] (reshaped to a value head's first, XLA wrote
    # the broadcast to HBM: 1.9 ms a step at 16 key heads of 32). A key
    # head a value head, the shapes are the value heads' own: a unit
    # axis there cost the backward a third solve a layer.
    kc = chunked(k, hk, dk)
    kk, rows, fade = _mm("bnhck,bnhjk->bnhcj", kc, kc), beta[..., None], decay
    if r > 1:
        kk = kk[:, :, :, None]
        rows, fade = (x.reshape(B, N, hk, r, *x.shape[3:])
                      for x in (rows, fade))
    a = jnp.where(i > j, rows * kk * fade, 0.0)
    eye = jnp.eye(chunk, dtype=F32)
    # (I + A)^-1 by forward substitution against the identity, float32;
    # then one operand of two MXU matmuls like any other.
    inv = lax.linalg.triangular_solve(
        a + eye, jnp.broadcast_to(eye, a.shape), left_side=True,
        lower=True, unit_diagonal=True).astype(dt).reshape(
            B, N, hv, chunk, chunk)
    hb = _token_major_step(hk, hv, dk, dv)
    if hb and use_pallas("gated_delta_rule", (q, k, v, g, beta),
                         _INTERPRET):
        return _kernel_rule(hk, hb, q, k, v, gamma, beta, inv)
    # chunk-major: a transposing copy each way, a key head repeated
    q, kc = (jnp.repeat(x, r, axis=2) if r > 1 else x
             for x in (chunked(q, hk, dk), kc))
    o = _rule_of_chunks(q, kc, chunked(v, hv, dv), gamma, beta, inv, decay)
    return jnp.moveaxis(o, 2, 3).reshape(B, T, hv * dv)
