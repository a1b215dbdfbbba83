"""The selective scan of a Mamba-1 state-space layer, forward and
backward (Gu & Dao, arXiv:2312.00752, section 3; Hugging Face
``modeling_jamba.py``'s slow path is the same recurrence).

A channel ``c`` of ``C`` carries ``N`` scalar states; from ``s_0 = 0``,
a token at a time::

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n s_t[n, c] C_t[n] + D[c] u_t[c]

The decay is diagonal in (n, c): ``N x C`` independent first-order
recurrences a token, no matmul form. Materialised over a sequence the
states are ``[T, N, C]`` float32 (2.7 GB at T 8192, C 5120, N 16); a
closed form over a chunk divides by a running product of decays that
underflows (``dt A`` reaches -11 a token). So the state is carried, and
what is kept for the backward pass is the operands and the state at each
chunk's start (``T / CHUNK x [N, C]`` float32, 21 MB for the sequence
above), from which the backward pass recomputes a chunk's states as it
reaches it, last chunk first, carrying the state's cotangent.

One recurrence, two carriers; which runs is read off the operands
(``ops/_platform.py``), never off an option:

- operands on a TPU (and ``C`` a multiple of 128): a Pallas kernel pair,
  ``hvd_ssm_scan_fwd`` / ``hvd_ssm_scan_bwd`` by their
  ``kernel_metadata``. A state is ``[N, channels]``: states on the
  sublanes, channels on the lanes, so that ``u``, ``dt`` and ``y`` are
  read and written where the projections leave them, ``[B, T, C]`` with
  tokens on the sublanes. The grid is ``(B, T / CHUNK, C / cb)``: a
  sequence's chunks one after another (``"arbitrary"``), and inside a
  chunk one block of ``cb`` channels after another, whose states live in
  a VMEM scratch ``[C / cb, N, cb]`` for the whole sequence and never
  cross HBM but at a chunk's start. (Channel blocks inside, not outside,
  the chunks: ``B_t`` and ``C_t`` belong to a token and are fetched once
  a chunk whatever the channels, and their gradients, sums over the
  channels, are accumulated in the output block while it stays.) A grid
  step walks its chunk token by token with a block's state in registers:
  all VPU and EUP work, seven multiply-adds and one exponential a state
  and token. ``B_t`` and ``C_t`` enter lane-dense, ``[B, T, N, 128]``
  float32 with a token's ``N`` numbers down the sublanes and repeated
  along the lanes (8 KB a token): a kernel cannot turn a row of ``N``
  into a column without the XLU, and a copy of 67 MB is a tenth of a
  millisecond. Their gradients leave the same way, summed over the lanes
  by the caller. Each kernel sits behind ONE jitted function, so a
  program lowers it once however many layers call it
  (``ops/gated_delta_rule.py`` says why);
- elsewhere: ``_scan``, a ``lax.scan`` over chunks of a ``lax.scan``
  over tokens, the inner one under ``jax.checkpoint``; differentiated by
  jax (its backward pass keeps a chunk's states, ``[CHUNK, B, N, C]``,
  and the chunks' first). The CPU's path and the tests' reference for
  the kernels, which run there in interpret mode under ``_INTERPRET``.

Precision: everything in float32 (``u``, ``B_t``, ``C_t`` are read as
float32; ``y`` and ``du`` are rounded to ``u``'s dtype as they leave);
every exponential has a non-positive argument.
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
# Tokens between two kept states, and the tokens of one grid step.
CHUNK = 128
# Channels a grid step carries in registers (a multiple of the 128
# lanes, or the largest such divisor of C under it): the state, the
# decay rates and, backward, two accumulators, [N, cb] float32 each.
CHANNELS_A_STEP = 1024
LANES, SUBLANES = 128, 8
# What the kernels may hold in VMEM: a chunk's blocks twice (the
# pipeline's two buffers) and, backward, the chunk's states.
VMEM_LIMIT = 64 * 1024 * 1024
# Tests flip this to run the kernel pair in pallas interpret mode on the
# CPU (as ``flash_attention._INTERPRET``).
_INTERPRET = False


# ---------------------------------------------------------------------
# The plain form.
# ---------------------------------------------------------------------

def _scan(u, dt, A, Bm, Cm):
    """``y`` [B, T, C] float32 of the recurrence without the ``D`` term:
    ``u``, ``dt`` [B, T, C], ``A`` [C, N], ``Bm``, ``Cm`` [B, T, N], all
    float32, ``T`` a multiple of ``CHUNK`` or shorter than one."""
    b, t, c = u.shape
    chunk = min(CHUNK, t)

    def token(s, x):
        u, dt, Bt, Ct = x                        # [B, C], [B, C], [B, N]
        s = jnp.exp(dt[..., None] * A) * s \
            + (dt * u)[..., None] * Bt[:, None, :]
        return s, jnp.sum(s * Ct[:, None, :], -1)

    def chunked(x):      # [B, T, ...] -> [T / chunk, chunk, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // chunk, chunk, *x.shape[1:])

    _, y = lax.scan(
        jax.checkpoint(lambda s, x: lax.scan(token, s, x)),
        jnp.zeros((b, c, A.shape[1]), F32),
        tuple(chunked(x) for x in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y.reshape(t, b, c), 0, 1)


# ---------------------------------------------------------------------
# The same recurrence as a Pallas TPU kernel pair: the state in VMEM.
# ---------------------------------------------------------------------

def _tiles(x):
    """A value [N, cb] -> its lane tiles, [N, 128] each."""
    return [x[:, j:j + LANES] for j in range(0, x.shape[1], LANES)]


def _rows(ref, base):
    """Rows ``base .. base + 7`` (``base`` a multiple of 8: a dynamic
    load has to be aligned) of a [CHUNK, cb] float32 block, a lane tile
    at a time: [8, 128] each."""
    return [ref[pl.ds(base, SUBLANES), j:j + LANES]
            for j in range(0, ref.shape[1], LANES)]


def _down(rows, k, states):
    """Row ``k`` of an [8, 128] value down ``states`` sublanes."""
    return jnp.broadcast_to(rows[k:k + 1], (states, LANES))


def _put(rows, k, row):
    """``rows`` [8, 128] with row ``k`` replaced by ``row`` [1, 128]:
    a select, so that the eight tokens' rows leave in one aligned
    store."""
    at = lax.broadcasted_iota(jnp.int32, rows.shape, 0) == k
    return jnp.where(at, jnp.broadcast_to(row, rows.shape), rows)


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest):
    """One grid step: a chunk of one block of channels from the state
    in ``s_ref[block]``, eight tokens a trip. ``rest`` = (states_ref,
    s_ref, uf_ref, yf_ref) where the chunks' first states are kept, else
    without the first."""
    s_ref, uf_ref, yf_ref = rest[-3:]
    n, blk = pl.program_id(1), pl.program_id(2)
    states = a_ref.shape[0]

    @pl.when(n == 0)
    def _start():
        s_ref[blk] = jnp.zeros(s_ref.shape[1:], F32)

    if len(rest) == 4:
        rest[0][...] = s_ref[blk]
    uf_ref[...] = u_ref[...].astype(F32)
    rates = _tiles(a_ref[...])

    def eight(i, s):
        base = pl.multiple_of(i * SUBLANES, SUBLANES)
        dts = _rows(dt_ref, base)
        dtus = [dt * u for dt, u in zip(dts, _rows(uf_ref, base))]
        ys = [jnp.zeros((SUBLANES, LANES), F32) for _ in rates]
        s = list(s)
        for k in range(SUBLANES):
            Bt, Ct = b_ref[base + k], c_ref[base + k]        # [N, 128]
            for j, rate in enumerate(rates):
                s[j] = jnp.exp(_down(dts[j], k, states) * rate) * s[j] \
                    + _down(dtus[j], k, states) * Bt
                ys[j] = _put(ys[j], k,
                             jnp.sum(s[j] * Ct, 0, keepdims=True))
        for j, y in enumerate(ys):
            yf_ref[pl.ds(base, SUBLANES), j * LANES:(j + 1) * LANES] = y
        return tuple(s)

    s = lax.fori_loop(0, u_ref.shape[0] // SUBLANES, eight,
                      tuple(_tiles(s_ref[blk])))
    s_ref[blk] = jnp.concatenate(s, 1)
    y_ref[...] = (yf_ref[...] + d_ref[...] * uf_ref[...]).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, states_ref,
                dy_ref, du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                h_ref, uf_ref, dyf_ref, duf_ref, before_ref):
    """The reverse pass: grid step ``n`` holds chunk ``N - 1 - n`` (the
    index maps count down). ``h_ref[block]``: the cotangent of the state
    the chunk ENDS with. First the chunk's states again, from the kept
    one (``before_ref[t]``: the state token ``t`` starts from), then its
    tokens from the last to the first, eight a trip::

        g_t   = h + C_t (x) dy_t            the cotangent of s_t
        dC_t  = sum_c s_t dy_t              dB_t = sum_c g_t dt_t u_t
        d(dt_t u_t) = sum_n g_t B_t
        d(dt_t A)   = g_t s_{t-1} exp(dt_t A)
        h     = exp(dt_t A) g_t             the cotangent of s_{t-1}
    """
    n, blk = pl.program_id(1), pl.program_id(2)
    states, chunk = a_ref.shape[0], u_ref.shape[0]

    @pl.when(n == 0)
    def _start():
        h_ref[blk] = jnp.zeros(h_ref.shape[1:], F32)

    @pl.when(blk == 0)
    def _first_block():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    uf_ref[...] = u_ref[...].astype(F32)
    dyf_ref[...] = dy_ref[...].astype(F32)
    rates = _tiles(a_ref[...])

    def again(i, s):
        base = pl.multiple_of(i * SUBLANES, SUBLANES)
        dts = _rows(dt_ref, base)
        dtus = [dt * u for dt, u in zip(dts, _rows(uf_ref, base))]
        s = list(s)
        for k in range(SUBLANES):
            Bt = b_ref[base + k]
            before_ref[base + k] = jnp.concatenate(s, 1)
            for j, rate in enumerate(rates):
                s[j] = jnp.exp(_down(dts[j], k, states) * rate) * s[j] \
                    + _down(dtus[j], k, states) * Bt
        return tuple(s)

    lax.fori_loop(0, chunk // SUBLANES, again,
                  tuple(_tiles(states_ref[...])))

    def eight(i, carry):
        h, d_rates = (list(x) for x in carry)
        base = pl.multiple_of(chunk - SUBLANES * (i + 1), SUBLANES)
        dts, us = _rows(dt_ref, base), _rows(uf_ref, base)
        dys = _rows(dyf_ref, base)
        dtus = [dt * u for dt, u in zip(dts, us)]
        d_dtus = [jnp.zeros((SUBLANES, LANES), F32) for _ in rates]
        ddts = [jnp.zeros((SUBLANES, LANES), F32) for _ in rates]
        for k in reversed(range(SUBLANES)):
            Bt, Ct = b_ref[base + k], c_ref[base + k]
            before = _tiles(before_ref[base + k])
            d_b = jnp.zeros((states, LANES), F32)
            d_c = jnp.zeros((states, LANES), F32)
            for j, rate in enumerate(rates):
                dt, dtu = _down(dts[j], k, states), _down(dtus[j], k, states)
                dy = _down(dys[j], k, states)
                decay = jnp.exp(dt * rate)
                g = h[j] + Ct * dy
                d_c = d_c + (decay * before[j] + dtu * Bt) * dy
                d_b = d_b + g * dtu
                d_decay = g * before[j] * decay
                d_dtus[j] = _put(d_dtus[j], k,
                                 jnp.sum(g * Bt, 0, keepdims=True))
                ddts[j] = _put(ddts[j], k,
                               jnp.sum(d_decay * rate, 0, keepdims=True))
                d_rates[j] = d_rates[j] + d_decay * dt
                h[j] = decay * g
            db_ref[base + k] += d_b
            dc_ref[base + k] += d_c
        for j, (d_dtu, ddt) in enumerate(zip(d_dtus, ddts)):
            lanes = slice(j * LANES, (j + 1) * LANES)
            ddt_ref[pl.ds(base, SUBLANES), lanes] = ddt + d_dtu * us[j]
            duf_ref[pl.ds(base, SUBLANES), lanes] = d_dtu * dts[j]
        return tuple(h), tuple(d_rates)

    zeros = tuple(jnp.zeros((states, LANES), F32) for _ in rates)
    h, d_rates = lax.fori_loop(0, chunk // SUBLANES, eight,
                               (tuple(_tiles(h_ref[blk])), zeros))
    h_ref[blk] = jnp.concatenate(h, 1)
    da_ref[...] = jnp.concatenate(d_rates, 1)
    du_ref[...] = (duf_ref[...] + d_ref[...] * dyf_ref[...]).astype(
        du_ref.dtype)


def _call(name, kernel, grid, in_specs, out_specs, out_shape, scratch,
          operands, interpret):
    """``metadata`` is the name a device trace shows of the call. A
    sequence's chunks one after another, a chunk's channel blocks one
    after another (the per-token blocks stay while they change)."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, scratch_shapes=scratch,
        metadata={"kernel": name},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(*operands)


def _specs(u, a, cb, at):
    """The block specs over ``u`` [B, T, C] and ``a`` [N, C], ``at(n)``
    the chunk grid step ``n`` takes: ``wide`` a chunk of a block of
    channels, ``rates`` the block's decay rates, ``token`` the
    lane-dense ``B_t`` / ``C_t`` of a chunk, ``kept`` one block of one
    chunk of ``[B, T / CHUNK, N, C]``, ``skip`` the block's ``D`` [1,
    C]."""
    states = a.shape[0]
    wide = pl.BlockSpec((None, CHUNK, cb), lambda b, n, c: (b, at(n), c))
    rates = pl.BlockSpec((states, cb), lambda b, n, c: (0, c))
    skip = pl.BlockSpec((1, cb), lambda b, n, c: (0, c))
    token = pl.BlockSpec((None, CHUNK, states, LANES),
                         lambda b, n, c: (b, at(n), 0, 0))
    kept = pl.BlockSpec((None, None, states, cb),
                        lambda b, n, c: (b, at(n), 0, c))
    return wide, rates, token, kept, skip


def _lane_dense(x):
    """``Bm`` / ``Cm`` [B, T, N] -> [B, T, N, 128] float32."""
    return jnp.broadcast_to(x.astype(F32)[..., None], x.shape + (LANES,))


@functools.partial(jax.jit, static_argnames=("keep", "cb", "interpret"))
def _kernel_fwd(u, dt, a, Bm, Cm, D, *, keep, cb, interpret):
    """-> [``y`` [B, T, C] in ``u``'s dtype], and with ``keep`` the
    state every chunk started from, [B, T / CHUNK, N, C] float32.
    ``a`` [N, C]; ``T`` a multiple of ``CHUNK``. Jitted on its own:
    every site that enters it with these shapes calls ONE lowered
    function."""
    with scope("hvd.ssm.core"):
        B, T, C = u.shape
        states, chunks = a.shape[0], T // CHUNK
        wide, rates, token, kept, skip = _specs(u, a, cb, lambda n: n)
        out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype)] + [
            jax.ShapeDtypeStruct((B, chunks, states, C), F32)] * keep
        return _call(
            "hvd_ssm_scan_fwd", _fwd_kernel, (B, chunks, C // cb),
            [wide, wide, rates, token, token, skip], [wide] + [kept] * keep,
            out_shape,
            [pltpu.VMEM((C // cb, states, cb), F32),
             pltpu.VMEM((CHUNK, cb), F32), pltpu.VMEM((CHUNK, cb), F32)],
            (u, dt, a, _lane_dense(Bm), _lane_dense(Cm), D[None]),
            interpret)


@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def _kernel_bwd(u, dt, a, Bm, Cm, D, kept_states, dy, *, cb, interpret):
    """-> (du, ddt, da [N, C], dBm, dCm, dD) in their operands' shapes
    and dtypes. ``dD``, a sum over every token, is the caller's."""
    with scope("hvd.ssm.core"):
        B, T, C = u.shape
        states, chunks = a.shape[0], T // CHUNK
        wide, rates, token, kept, skip = _specs(
            u, a, cb, lambda n: chunks - 1 - n)
        dy = dy.astype(u.dtype)
        lane_dense = jax.ShapeDtypeStruct((B, T, states, LANES), F32)
        du, ddt, da, db, dc = _call(
            "hvd_ssm_scan_bwd", _bwd_kernel, (B, chunks, C // cb),
            [wide, wide, rates, token, token, skip, kept, wide],
            [wide, wide, kept, token, token],
            [jax.ShapeDtypeStruct(u.shape, u.dtype),
             jax.ShapeDtypeStruct(u.shape, F32),
             jax.ShapeDtypeStruct((B, chunks, states, C), F32),
             lane_dense, lane_dense],
            [pltpu.VMEM((C // cb, states, cb), F32)]
            + [pltpu.VMEM((CHUNK, cb), F32)] * 3
            + [pltpu.VMEM((CHUNK, states, cb), F32)],
            (u, dt, a, _lane_dense(Bm), _lane_dense(Cm), D[None],
             kept_states, dy), interpret)
        return (du, ddt.astype(dt.dtype), da.sum((0, 1)).astype(a.dtype),
                db.sum(-1).astype(Bm.dtype), dc.sum(-1).astype(Cm.dtype),
                jnp.sum(dy.astype(F32) * u.astype(F32), (0, 1)
                        ).astype(D.dtype))


def _step(u):
    """What a grid step takes of these operands, and how it runs."""
    lanes = u.shape[-1] // LANES
    return {"cb": LANES * _pick_block(lanes, CHANNELS_A_STEP // LANES),
            "interpret": _INTERPRET}


@jax.custom_vjp
def _kernel_scan(u, dt, a, Bm, Cm, D):
    """``_scan`` plus ``D u`` on ``a`` = ``A`` transposed, [N, C], by
    the kernels: ``y`` in ``u``'s dtype."""
    return _kernel_fwd(u, dt, a, Bm, Cm, D, keep=False, **_step(u))[0]


def _kernel_scan_fwd(u, dt, a, Bm, Cm, D):
    y, kept = _kernel_fwd(u, dt, a, Bm, Cm, D, keep=True, **_step(u))
    return y, (u, dt, a, Bm, Cm, D, kept)


def _kernel_scan_bwd(res, dy):
    return _kernel_bwd(*res, dy, **_step(res[0]))


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def selective_scan(u, dt, A, Bm, Cm, D):
    """``y`` [B, T, C], in ``u``'s dtype, of the recurrence above for
    ``u`` [B, T, C] (the convolved, activated input), the step sizes
    ``dt`` [B, T, C] (positive: after the softplus), the decay rates
    ``A`` [C, N] (negative), a token's input and output maps ``Bm``,
    ``Cm`` [B, T, N] and the skip ``D`` [C]. Differentiable in all six.
    Any ``T``: a sequence is padded to whole chunks with tokens of
    ``dt`` = 0, which neither decay nor write."""
    T, C = u.shape[1:]
    dt, A, D = (x.astype(F32) for x in (dt, A, D))
    kernels = C % LANES == 0 and use_pallas(
        "selective_scan", (u, dt, A, Bm, Cm, D), _INTERPRET)
    pad = -T % CHUNK if kernels or T > CHUNK else 0
    if pad:
        u, dt, Bm, Cm = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                         for x in (u, dt, Bm, Cm))
    if kernels:
        return _kernel_scan(u, dt, A.T, Bm, Cm, D)[:, :T]
    u32 = u.astype(F32)
    y = _scan(u32, dt, A, Bm.astype(F32), Cm.astype(F32)) + D * u32
    return y[:, :T].astype(u.dtype)
