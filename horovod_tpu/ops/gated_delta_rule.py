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

Everything that does not depend on ``S`` (``U``, ``W``, the in-chunk
scores, the decayed ``Q`` and ``K``) is computed for ALL chunks at once,
by ordinary batched operations that autodiff differentiates. Only the
three lines that read ``S`` run chunk after chunk: ``_carry_state``,
MXU matmuls over the T/C chunks with the ``[B, H, dk, dv]`` state
carried, and a ``custom_vjp``: its forward keeps the state each chunk
STARTED from (T/C x [B, H, dk, dv] float32: 537 MB at B2 T8192 H32 d128,
alive for one layer's backward under the layer's remat) and its backward
is one reverse pass that recomputes ``V'`` (one matmul) and carries the
state's cotangent. Kept and not recomputed: the states are what a
recomputation would have to run the whole forward pass again for, and
one layer's are a thirtieth of the chip.

One algorithm, two carriers; which one runs is read off the operands
(``ops/_platform.py``, as the flash kernels decide it), never off an
option:

- operands on a TPU: a Pallas kernel pair. ``hvd_gdn_state_fwd`` walks a
  grid ``(B, H / hb, T / C)`` whose last axis, the chunks, is sequential;
  the state of ``hb`` heads lives in a float32 VMEM scratch for the whole
  sequence and never crosses HBM (but where the states are kept).
  ``hvd_gdn_state_bwd`` walks the same grid from the last chunk to the
  first with the state's cotangent in the scratch. Both read the factors
  where the stage above leaves them, ``[B, N, H, C, ...]`` (an index map
  takes block ``(b, n, h)`` as readily as ``(n, b, h)``); only the kept
  states are chunk-major. The names are the calls' ``kernel_metadata``,
  what a device trace shows of them. Each kernel sits behind ONE jitted
  function: every layer and phase of a program calls one lowered copy (a
  ``pallas_call`` is lowered to Mosaic wherever it is traced, compile
  cache or not, and a set-up pays for each);
- elsewhere: ``_scan_state``, a ``lax.scan`` over chunk-major operands
  (the CPU's path, and the tests' reference for the kernels, which run
  there in interpret mode under ``_INTERPRET``).

Precision: the decays (``gamma``, every ``exp``, all of non-positive
arguments, so none overflows), the inverse (forward substitution, not a
Neumann series: stable whatever the keys) and the state are float32; the
matmuls take operands in the compute dtype (``q``'s; the inverse, the
decayed ``Q`` and ``K`` and the state rounded to it as they enter one)
and accumulate in float32.
"""

import functools

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
# The same pass as a Pallas TPU kernel pair: the state in VMEM.
# ---------------------------------------------------------------------

def _fwd_kernel(qg_ref, p_ref, u_ref, w_ref, kd_ref, dc_ref, o_ref, *rest):
    """One grid step: a chunk of ``hb`` heads from the state in
    ``S_ref`` [hb, dk, dv] float32, which lives across the chunk axis
    (the last, sequential one). ``_chunk``'s lines, rounding where it
    rounds, the heads the batch of a batched matmul. ``rest`` =
    (states_ref, S_ref) where the states are kept, else (S_ref,)."""
    S_ref = rest[-1]
    n = pl.program_id(2)
    dt = qg_ref.dtype

    @pl.when(n == 0)
    def _start():
        S_ref[...] = jnp.zeros_like(S_ref)

    S = S_ref[...]
    if len(rest) == 2:
        rest[0][...] = S
    Sd = S.astype(dt)
    new = (u_ref[...].astype(F32)
           - _mm("hck,hkv->hcv", w_ref[...], Sd)).astype(dt)
    o_ref[...] = (_mm("hck,hkv->hcv", qg_ref[...], Sd)
                  + _mm("hcj,hjv->hcv", p_ref[...], new)
                  ).astype(o_ref.dtype)
    S_ref[...] = dc_ref[:, pl.ds(n, 1), :] * S \
        + _mm("hck,hcv->hkv", kd_ref[...], new)


def _bwd_kernel(qg_ref, p_ref, u_ref, w_ref, kd_ref, dc_ref, states_ref,
                do_ref, dqg_ref, dp_ref, du_ref, dw_ref, dkd_ref, ddc_ref,
                dS_ref):
    """The reverse pass: grid step ``n`` holds chunk ``N - 1 - n`` (the
    index maps count down); ``dS_ref`` is the cotangent of the state
    that chunk ends with. ``_scan_state_bwd``'s ``step``."""
    n = pl.program_id(2)
    row = pl.ds(pl.num_programs(2) - 1 - n, 1)
    dt = qg_ref.dtype

    @pl.when(n == 0)
    def _start():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    S, dS = states_ref[...], dS_ref[...]
    Sd, dSd = S.astype(dt), dS.astype(dt)
    do, w = do_ref[...], w_ref[...]
    new = (u_ref[...].astype(F32)
           - _mm("hck,hkv->hcv", w, Sd)).astype(dt)
    dnew = (_mm("hcj,hcv->hjv", p_ref[...], do)
            + _mm("hck,hkv->hcv", kd_ref[...], dSd)).astype(dt)
    dqg_ref[...] = _mm("hcv,hkv->hck", do, Sd).astype(dqg_ref.dtype)
    dp_ref[...] = _mm("hcv,hjv->hcj", do, new).astype(dp_ref.dtype)
    du_ref[...] = dnew.astype(du_ref.dtype)
    dw_ref[...] = (-_mm("hcv,hkv->hck", dnew, Sd)).astype(dw_ref.dtype)
    dkd_ref[...] = _mm("hcv,hkv->hck", new, dSd).astype(dkd_ref.dtype)
    # dc's gradient, sum(S * dS): summed over dk here and over dv by the
    # caller, a row of a block that stays for the whole sequence
    ddc_ref[:, row, :] = jnp.sum(S * dS, axis=1, keepdims=True)
    dS_ref[...] = dc_ref[:, row, :] * dS \
        + _mm("hck,hcv->hkv", qg_ref[...], do) \
        - _mm("hck,hcv->hkv", w, dnew)


def _call(name, kernel, hb, operands, grid, in_specs, out_specs, out_shape,
          interpret):
    """``metadata`` is the name a device trace shows of the call
    (``ops/flash_attention.py:_pallas_dispatch``). Batch and heads in
    any order, a sequence's chunks one after another; the scratch is the
    state (or its cotangent) of a step's heads."""
    dk, dv = operands[0].shape[-1], operands[2].shape[-1]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)


def _specs(qg, u, hb, at):
    """(grid, and the block specs) of ``hb`` heads of a chunk a step
    over ``[B, N, H, C, ...]`` operands, ``at(n)`` the chunk grid step
    ``n`` takes: ``chunks(last)`` over an operand ``last`` wide,
    ``rows`` over ``dc``'s ``[B, H, N, dv]`` (a sequence's stay in
    VMEM), ``states`` over the kept ``[N, B, H, dk, dv]``."""
    B, N, H, C, dk = qg.shape
    dv = u.shape[-1]

    def chunks(last):
        return pl.BlockSpec((None, None, hb, C, last),
                            lambda b, h, n: (b, at(n), h, 0, 0))

    rows = pl.BlockSpec((None, hb, N, dv), lambda b, h, n: (b, h, 0, 0))
    states = pl.BlockSpec((None, None, hb, dk, dv),
                          lambda b, h, n: (at(n), b, h, 0, 0))
    return (B, H // hb, N), chunks, rows, states


def _rows(dc, dv):
    """``dc`` [B, N, H] -> [B, H, N, dv] float32: a lane-dense row a
    chunk, which a kernel broadcasts down a state's ``dk`` sublanes."""
    B, N, H = dc.shape
    return jnp.broadcast_to(jnp.swapaxes(dc, 1, 2)[..., None],
                            (B, H, N, dv))


@functools.partial(jax.jit, static_argnames=("keep", "hb", "interpret"))
def _kernel_fwd(qg, p, u, w, kd, dc, *, keep, hb, interpret):
    """-> [``o`` [B, N, H, C, dv]], and with ``keep`` the state every
    chunk started from, [N, B, H, dk, dv] float32. Jitted on its own:
    every site that enters it with these shapes calls ONE lowered
    function, so a program pays a Mosaic lowering a form and not one a
    site (three layers, each forward, forward again under remat, and
    backward). The scope again: a shared body's name stack starts at
    this function; the call site's (its scope, its phase) stands before
    it only where the compiler inlines the call."""
    with scope("hvd.gdn.core"):
        B, N, H, _, dk = qg.shape
        dv = u.shape[-1]
        grid, chunks, rows, states = _specs(qg, u, hb, lambda n: n)
        out_specs = [chunks(dv)] + [states] * keep
        out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype)] + [
            jax.ShapeDtypeStruct((N, B, H, dk, dv), F32)] * keep
        return _call("hvd_gdn_state_fwd", _fwd_kernel, hb,
                     (qg, p, u, w, kd, _rows(dc, dv)), grid,
                     [chunks(x.shape[-1]) for x in (qg, p, u, w, kd)]
                     + [rows], out_specs, out_shape, interpret)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _kernel_bwd(qg, p, u, w, kd, dc, states, do, *, hb, interpret):
    """-> the six gradients, in their operands' shapes and dtypes."""
    with scope("hvd.gdn.core"):
        B, N, H = dc.shape
        dv = u.shape[-1]
        grid, chunks, rows, kept = _specs(qg, u, hb, lambda n: N - 1 - n)
        operands = (qg, p, u, w, kd)
        blocks = [chunks(x.shape[-1]) for x in operands]
        *grads, ddc = _call(
            "hvd_gdn_state_bwd", _bwd_kernel, hb,
            operands + (_rows(dc, dv), states, do.astype(qg.dtype)), grid,
            blocks + [rows, kept, chunks(dv)], blocks + [rows],
            [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands]
            + [jax.ShapeDtypeStruct((B, H, N, dv), F32)], interpret)
        return (*grads, jnp.swapaxes(ddc.sum(-1), 1, 2).astype(dc.dtype))


def _step(qg):
    """What a grid step takes of these operands, and how it runs."""
    return {"hb": _pick_block(qg.shape[2], HEADS_A_STEP),
            "interpret": _INTERPRET}


@jax.custom_vjp
def _kernel_state(qg, p, u, w, kd, dc):
    """``_scan_state`` on operands as the factors lie, ``[B, N, H, C,
    ...]`` and ``dc`` [B, N, H] -> ``o`` [B, N, H, C, dv]: the kernels
    take block ``(b, n, h)`` where the scan wants chunk ``n`` first."""
    return _kernel_fwd(qg, p, u, w, kd, dc, keep=False, **_step(qg))[0]


def _kernel_state_fwd(qg, p, u, w, kd, dc):
    o, states = _kernel_fwd(qg, p, u, w, kd, dc, keep=True, **_step(qg))
    return o, (qg, p, u, w, kd, dc, states)


def _kernel_state_bwd(res, do):
    return _kernel_bwd(*res, do, **_step(res[0]))


_kernel_state.defvjp(_kernel_state_fwd, _kernel_state_bwd)


def _carry_state(qg, p, u, w, kd, dc):
    """The state-carrying pass on operands ``[B, N, H, C, ...]`` (``dc``
    [B, N, H]) -> ``o`` [B, N, H, C, dv]. Operands on a TPU: the kernel
    pair. Elsewhere the scan, which wants them chunk-major."""
    operands = (qg, p, u, w, kd, dc)
    if use_pallas("gated_delta_rule", operands, _INTERPRET):
        return _kernel_state(*operands)
    return jnp.moveaxis(
        _scan_state(*(jnp.moveaxis(x, 1, 0) for x in operands)), 0, 1)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """``o`` [B, T, H, dv] of the rule above for ``q``, ``k`` [B, T, H,
    dk] (as they enter the rule: normalised, ``q`` scaled), ``v`` [B, T,
    H, dv], the log-decays ``g`` <= 0 and the write strengths ``beta``
    [B, T, H] (read as float32). ``T`` is a multiple of ``chunk``.
    Differentiable in all five. One head a value head: a key head that
    serves several is repeated by the caller."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if T % chunk:
        raise ValueError(
            f"gated_delta_rule works in chunks of {chunk} tokens: a "
            f"sequence of {T} is no multiple (pad it; a padded token "
            "with beta = 0 and g = 0 writes and forgets nothing)")
    N, dt = T // chunk, q.dtype

    def chunked(x):
        """[B, T, H, ...] -> [B, N, H, C, ...]"""
        return jnp.moveaxis(x.reshape(B, N, chunk, *x.shape[2:]), 2, 3)

    q, k, v, g, beta = (chunked(x) for x in (
        q, k, v, g.astype(F32), beta.astype(F32)))
    gamma = jnp.cumsum(g, -1)                                # [B,N,H,C]
    i, j = lax.iota(jnp.int32, chunk)[:, None], lax.iota(jnp.int32, chunk)
    # exp(gamma_i - gamma_j) where i >= j, 0 above the diagonal
    decay = jnp.exp(jnp.where(i >= j, gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    kk = _mm("bnhck,bnhjk->bnhcj", k, k)
    a = jnp.where(i > j, beta[..., None] * kk * decay, 0.0)
    eye = jnp.eye(chunk, dtype=F32)
    # (I + A)^-1 by forward substitution against the identity, float32;
    # then one operand of two MXU matmuls like any other.
    inv = lax.linalg.triangular_solve(
        a + eye, jnp.broadcast_to(eye, a.shape), left_side=True,
        lower=True, unit_diagonal=True).astype(dt)
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
    o = _carry_state(*operands)
    # [B, N, H, C, dv] -> [B, T, H, dv]
    return jnp.moveaxis(o, 2, 3).reshape(B, T, H, dv)

