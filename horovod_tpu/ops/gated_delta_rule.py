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
three lines that read ``S`` run chunk after chunk: ``_carry_state``, a
``lax.scan`` of MXU matmuls over the T/C chunks with the ``[B, H, dk,
dv]`` state as its carry, and a ``custom_vjp``: its forward keeps the
state each chunk STARTED from (T/C x [B, H, dk, dv] float32: 537 MB at
B2 T8192 H32 d128, alive for one layer's backward under the layer's
remat) and its backward is one reverse scan that recomputes ``V'`` (one
matmul) and carries the state's cotangent. Kept and not recomputed: the
states are what a recomputation would have to run the whole forward scan
again for, and one layer's are a thirtieth of the chip.

Precision: the decays (``gamma``, every ``exp``, all of non-positive
arguments, so none overflows), the inverse (forward substitution, not a
Neumann series: stable whatever the keys) and the state are float32; the
matmuls take operands in the compute dtype (``q``'s; the inverse, the
decayed ``Q`` and ``K`` and the state rounded to it as they enter one)
and accumulate in float32.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
CHUNK = 64


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
def _carry_state(qg, p, u, w, kd, dc):
    """The state-carrying pass. Chunk-major operands, ``N`` chunks of
    ``C`` tokens: ``qg`` = ``Q * exp(gamma)``, ``w``, ``kd`` = ``K *
    exp(gamma_C - gamma)`` [N, B, H, C, dk]; ``u`` [N, B, H, C, dv];
    ``p`` the decayed causal in-chunk scores [N, B, H, C, C]; ``dc`` =
    ``exp(gamma_C)`` [N, B, H] float32 -> ``o`` [N, B, H, C, dv] in
    ``u``'s dtype."""
    _, o = lax.scan(_chunk, _zero_state(qg, u),
                    _steps((qg, p, u, w, kd, dc)))
    return o.astype(u.dtype)


def _carry_state_fwd(qg, p, u, w, kd, dc):
    def step(S, x):
        S_next, o = _chunk(S, x)
        return S_next, (o, S)

    _, (o, states) = lax.scan(step, _zero_state(qg, u),
                              _steps((qg, p, u, w, kd, dc)))
    return o.astype(u.dtype), (qg, p, u, w, kd, dc, states)


def _carry_state_bwd(res, do):
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


_carry_state.defvjp(_carry_state_fwd, _carry_state_bwd)


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
    o = _carry_state(*(jnp.moveaxis(x, 1, 0) for x in operands))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, T, H, dv)

