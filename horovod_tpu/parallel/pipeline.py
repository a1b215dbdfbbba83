"""GPipe-style pipeline parallelism over the mesh's "pipe" axis.

Reference analog: none — Horovod is data-parallel only (SURVEY.md §5.7/
§2.6); this is net-new TPU machinery like ring attention. Design: the
layer stack is split into S contiguous stages (the stacked layer axis
shards over "pipe", so each device holds its stage's weights); inside a
*partial-manual* ``shard_map`` (manual over "pipe" only — tensor/fsdp/
data stay with GSPMD), a ``lax.scan`` runs the classic GPipe schedule:
each step every stage processes one microbatch and ``ppermute`` rotates
activations to the next stage. M microbatches drain in M + S - 1 steps
(the bubble); results collect on the last stage and are shared back with
a masked ``psum``.
"""

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _pipe_spmd(inner, mesh, axis, split_in, split_out):
    """Run ``inner`` manual over the pipe axis: the partial-manual
    ``shard_map`` the docstring above describes.

    ``split_in`` / ``split_out`` are per-argument booleans: True means
    the leading dim splits over ``axis`` (shard_map spec ``P(axis)``),
    False means replicated (``P()``).

    The ``shard_map`` comes back under ``jax.jit``: a partial-manual
    ``shard_map`` (``axis_names`` a subset of the mesh's) refuses an
    eager call ("out_specs refers to 'data'..."), and an eval loop that
    logs ``llama_loss`` without a surrounding jit is such a call. Under
    a caller's jit the inner one is inlined; an eager caller compiles
    it anew each call (``inner`` is a fresh closure), so a step that
    runs more than once belongs under ``jax.jit``.
    """
    return jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=tuple(P(axis) if s else P() for s in split_in),
        out_specs=tuple(P(axis) if s else P() for s in split_out),
        axis_names={axis}, check_vma=False))


def _cast_f32_on_cpu(mesh, xs):
    """XLA CPU's AllReducePromotion pass crashes on the bf16 allreduces
    the pipeline schedules generate (collection/cotangent psums inside
    manual collectives): on jaxlib 0.9.0 the differentiated gpipe
    schedule aborts the process with ``hlo_instruction.cc:1585] Invalid
    binary instruction opcode copy`` out of
    ``xla::AllReducePromotion::RunImpl()``
    (``test_llama.py::test_pipeline_bf16_compiles_on_cpu`` without this
    cast, PR 46). CPU is the test substrate, so run the schedule
    in f32 there — TPU keeps native bf16. Returns ``(xs, dtype to cast
    schedule outputs back to, or None)``; shared by gpipe /
    one_f_one_b / interleaved_one_f_one_b so the workaround cannot
    drift between schedules."""
    on_cpu = all(d.platform == "cpu" for d in mesh.devices.flat)
    if on_cpu and xs.dtype in (jnp.bfloat16, jnp.float16):
        return xs.astype(jnp.float32), xs.dtype
    return xs, None


def _gpipe_inner(stage_fn, S, M, axis):
    """The per-device GPipe program (manual over ``axis``): a
    ``[M + S - 1]``-step scan with one activation ``ppermute`` per step,
    then the result/aux ``psum`` pair. Factored out of :func:`gpipe` so
    hvdlint can trace it standalone (``jax.make_jaxpr`` with
    ``axis_env=[(axis, S)]``) and check it against
    :func:`predicted_collectives` — see ``horovod_tpu/analysis/``."""

    def inner(sp, xs_):
        stage = lax.axis_index(axis)

        def step(state, t):
            carry, buf, aux = state
            inj = lax.dynamic_index_in_dim(xs_, jnp.clip(t, 0, M - 1), 0,
                                           keepdims=False)
            inp = jnp.where(stage == 0, inj, carry)
            out, a = stage_fn(sp, inp)
            # Bubble steps (stage s idle before t=s and after t=s+M-1)
            # compute on garbage; mask their aux and never collect them.
            valid = (t - stage >= 0) & (t - stage < M)
            aux = aux + jnp.where(valid, a, 0.0)
            cidx = jnp.clip(t - (S - 1), 0, M - 1)
            collect = (stage == S - 1) & (t >= S - 1)
            cur = lax.dynamic_index_in_dim(buf, cidx, 0, keepdims=False)
            buf = lax.dynamic_update_index_in_dim(
                buf, jnp.where(collect, out, cur), cidx, 0)
            carry = lax.ppermute(out, axis,
                                 [(i, (i + 1) % S) for i in range(S)])
            return (carry, buf, aux), None

        init = (jnp.zeros_like(xs_[0]), jnp.zeros_like(xs_),
                jnp.zeros((), jnp.float32))
        (carry, buf, aux), _ = lax.scan(step, init, jnp.arange(M + S - 1))
        # Results live on the last stage; the loss is computed globally,
        # so share them (and the aux total) across the pipe axis. The
        # psum runs in f32 for sub-f32 activations: XLA CPU's
        # AllReducePromotion pass crashes on bf16 allreduce inside
        # manual shard_map, and on TPU the f32 cast is fused anyway.
        out_dt = buf.dtype
        masked = jnp.where(stage == S - 1, buf, jnp.zeros_like(buf))
        if out_dt in (jnp.bfloat16, jnp.float16):
            buf = lax.psum(masked.astype(jnp.float32), axis).astype(out_dt)
        else:
            buf = lax.psum(masked, axis)
        aux = lax.psum(aux, axis)
        return buf, aux

    return inner


def gpipe(stage_fn, stage_params, xs, mesh, axis="pipe"):
    """Run microbatches through the pipeline.

    ``stage_fn(stage_params_block, x_mb) -> (y_mb, aux)`` applies ONE
    stage's slice of the network (aux is a scalar, e.g. an MoE balance
    loss; return 0.0 if unused). ``stage_params`` is a pytree whose
    leaves have a leading stacked-layer axis of length divisible by the
    pipe size — ``shard_map`` splits it into per-stage blocks.
    ``xs`` is ``[M, ...]`` microbatches. Returns ``(ys [M, ...],
    aux_sum)`` where aux_sum totals stage_fn aux over all (stage,
    microbatch) pairs.
    """
    S = mesh.shape[axis]
    M = xs.shape[0]
    xs, cast_dt = _cast_f32_on_cpu(mesh, xs)

    inner = _gpipe_inner(stage_fn, S, M, axis)
    ys, aux = _pipe_spmd(inner, mesh, axis, (True, False),
                         (False, False))(stage_params, xs)
    if cast_dt is not None:
        ys = ys.astype(cast_dt)
    return ys, aux


def one_f_one_b(stage_fn, loss_fn, stage_params, head_params, xs,
                loss_args, mesh, axis="pipe", aux_cotangent=0.0):
    """1F1B pipeline schedule: forward AND backward interleaved in one
    lockstep scan, with the loss computed on the last stage per
    microbatch.

    Why not let AD differentiate :func:`gpipe`? Its backward replays
    the forward scan in reverse, so every stage stashes activations for
    ALL M microbatches — O(M) memory. Here each slot runs one forward
    subtick and one backward subtick per stage: stage ``s`` forwards
    microbatch ``m`` at slot ``s + m``, the last stage turns it
    straight into a loss cotangent, and the backward walks back up at
    slot ``2(S-1) - s + m``. A stage therefore holds at most
    ``min(M, 2(S-1-s) + 1) <= 2S - 1`` stashed INPUTS (activations are
    recomputed from the stashed input during the backward subtick —
    per-stage remat, the standard 1F1B trade). Timeline = ``M + 2(S-1)``
    slots; the ``2(S-1)/(M + 2(S-1))`` bubble fraction matches GPipe's
    forward+backward total, so the win is memory, not bubble.

    ``stage_fn(sp_block, x_mb) -> (y_mb, aux_scalar)`` as in gpipe.
    ``loss_fn(head_params, y_mb, loss_args_mb) -> scalar`` is the last
    stage's per-microbatch objective NUMERATOR (any global
    normalization — e.g. a mask-token count — must be folded in by the
    caller, since microbatches cannot see each other's denominators).
    ``loss_args`` is a pytree with leading microbatch axis M (targets,
    masks, ...). ``aux_cotangent`` is the constant d(objective)/d(aux)
    applied to every valid (stage, microbatch) aux contribution — e.g.
    ``moe_aux_weight / (n_layers * M)``.

    Returns ``(loss_sum, aux_sum, d_stage_params, d_head_params,
    d_xs)`` — the gradient of ``loss_sum + aux_cotangent * aux_raw_sum``
    with respect to (stage_params, head_params, xs). Callers wanting
    plain ``value_and_grad`` ergonomics should wrap this in a
    ``custom_vjp`` (see models/llama.py's 1f1b path).

    Reference analog: none (net-new, like gpipe); the schedule is the
    public non-interleaved 1F1B (PipeDream-flush) formulation.
    """
    S = mesh.shape[axis]
    M = xs.shape[0]
    xs, cast_dt = _cast_f32_on_cpu(mesh, xs)

    inner = _one_f_one_b_inner(stage_fn, loss_fn, S, M, axis,
                               aux_cotangent)
    d_sp, d_hp, d_xs, loss, aux = _pipe_spmd(
        inner, mesh, axis, (True, False, False, False),
        (True, False, False, False, False))(
            stage_params, head_params, xs, loss_args)
    if cast_dt is not None:
        d_xs = d_xs.astype(cast_dt)
    return loss, aux, d_sp, d_hp, d_xs


def _one_f_one_b_inner(stage_fn, loss_fn, S, M, axis, aux_cotangent):
    """The per-device lockstep-1F1B program (manual over ``axis``): a
    ``[M + 2(S-1)]``-slot scan with one forward and one backward
    activation ``ppermute`` per slot, then the shared-gradient ``psum``
    tail (head-param leaves, d_xs, loss, aux — stage params stay
    local). Factored out of :func:`one_f_one_b` so hvdlint can trace it
    standalone against :func:`predicted_collectives`."""
    Q = min(M, 2 * S - 1)                       # stash depth per stage
    U = M + 2 * (S - 1)                         # total slots

    def inner(sp, hp, xs_, largs_):
        stage = lax.axis_index(axis)
        is_last = stage == S - 1

        def slot(state, u):
            (fwd_carry, bwd_carry, stash, d_sp, d_hp, d_xs, loss,
             aux) = state

            # ---- forward subtick ----
            m_f = u - stage
            f_valid = (m_f >= 0) & (m_f < M)
            mf_c = jnp.clip(m_f, 0, M - 1)
            inj = lax.dynamic_index_in_dim(xs_, mf_c, 0, keepdims=False)
            x_in = jnp.where(stage == 0, inj, fwd_carry)
            out, a = stage_fn(sp, x_in)
            aux = aux + jnp.where(f_valid, a, 0.0)

            # Last stage: microbatch loss + its cotangents wrt the
            # stage output AND the head params, all from ONE
            # linearization of the loss head (it contains the
            # [mb,T,D]@[D,vocab] logits matmul — the model's largest —
            # so a second grad call would double the head work every
            # slot). Both are consumed by THIS slot's backward subtick
            # (the last stage's backward slot equals its forward slot).
            la = jax.tree.map(
                lambda t: lax.dynamic_index_in_dim(t, mf_c, 0,
                                                   keepdims=False),
                largs_)
            lval, (g_last, d_hp_m) = jax.value_and_grad(
                lambda o, h: loss_fn(h, o, la), argnums=(0, 1))(out, hp)
            lvalid = is_last & f_valid
            loss = loss + jnp.where(lvalid, lval, 0.0)
            d_hp = jax.tree.map(
                lambda acc, gm: acc + jnp.where(lvalid, gm, 0),
                d_hp, d_hp_m)

            pos_f = mf_c % Q
            old = lax.dynamic_index_in_dim(stash, pos_f, 0,
                                           keepdims=False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_valid, x_in, old), pos_f, 0)
            fwd_carry = lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)])

            # ---- backward subtick ----
            m_b = u - (2 * (S - 1) - stage)
            b_valid = (m_b >= 0) & (m_b < M)
            mb_c = jnp.clip(m_b, 0, M - 1)
            x_b = lax.dynamic_index_in_dim(stash, mb_c % Q, 0,
                                           keepdims=False)
            g_in = jnp.where(is_last, g_last, bwd_carry)
            _, pull = jax.vjp(stage_fn, sp, x_b)
            d_sp_m, dx = pull((g_in,
                               jnp.where(b_valid,
                                         jnp.float32(aux_cotangent),
                                         0.0)))
            d_sp = jax.tree.map(
                lambda acc, gm: acc + jnp.where(b_valid, gm, 0),
                d_sp, d_sp_m)
            # Stage 0's dx is the gradient wrt xs[m_b].
            cur = lax.dynamic_index_in_dim(d_xs, mb_c, 0, keepdims=False)
            d_xs = lax.dynamic_update_index_in_dim(
                d_xs, jnp.where((stage == 0) & b_valid, dx, cur), mb_c,
                0)
            bwd_carry = lax.ppermute(
                dx, axis, [(i, (i - 1) % S) for i in range(S)])
            return (fwd_carry, bwd_carry, stash, d_sp, d_hp, d_xs,
                    loss, aux), None

        mb_shape = xs_[0]
        init = (jnp.zeros_like(mb_shape), jnp.zeros_like(mb_shape),
                jnp.zeros((Q,) + mb_shape.shape, mb_shape.dtype),
                jax.tree.map(jnp.zeros_like, sp),
                jax.tree.map(jnp.zeros_like, hp),
                jnp.zeros_like(xs_),
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        (_, _, _, d_sp, d_hp, d_xs, loss, aux), _ = lax.scan(
            slot, init, jnp.arange(U))

        def share(x):
            # Sum across the pipe axis; f32 for sub-f32 payloads (the
            # CPU AllReducePromotion crash, as in gpipe).
            if x.dtype in (jnp.bfloat16, jnp.float16):
                return lax.psum(x.astype(jnp.float32),
                                axis).astype(x.dtype)
            return lax.psum(x, axis)

        # d_sp stays stage-local (out_specs P(axis) reassembles the
        # stacked layer axis); everything else is summed — each piece
        # is nonzero on exactly one stage.
        d_hp = jax.tree.map(share, d_hp)
        d_xs = share(d_xs)
        loss = lax.psum(loss, axis)
        aux = lax.psum(aux, axis)
        return d_sp, d_hp, d_xs, loss, aux

    return inner


# ---- interleaved (virtual-stage) 1F1B --------------------------------

class InterleavedSchedule(NamedTuple):
    """Host-built slot tables for the interleaved 1F1B engine.

    Every slot is ONE subtick: each device either forwards one (chunk,
    microbatch), backwards one, or idles — unlike :func:`one_f_one_b`,
    whose lockstep slots always pay a forward AND a backward subtick
    and therefore match GPipe's bubble. All tables are ``[n_slots, S]``
    int32, indexed by the receiving/acting device.
    """
    S: int
    V: int
    M: int
    n_slots: int
    stash_depth: int          # activation-ring slots per chunk (Q)
    ctg_depth: int            # cotangent-ring slots per chunk (Qb)
    kind: np.ndarray          # 0=forward, 1=backward, 2=idle,
    #                           3=forward+loss-head (final global stage)
    chunk: np.ndarray         # acting chunk v (0 when idle)
    mb: np.ndarray            # acting microbatch m (0 when idle)
    stash_idx: np.ndarray     # v*Q  + m%Q   of the acting task
    ctg_idx: np.ndarray       # v*Qb + m%Qb  of the acting task
    rf_valid: np.ndarray      # activation arriving on the fwd carry?
    rf_idx: np.ndarray        # its stash slot (v*Q + m%Q)
    rb_valid: np.ndarray      # cotangent arriving on the bwd carry?
    rb_idx: np.ndarray        # its ctg slot (v*Qb + m%Qb)

    @property
    def bubble_fraction(self):
        """Idle subticks / total subticks over the whole schedule (each
        device runs ``2*M*V`` useful chunk-subticks in ``n_slots``)."""
        return 1.0 - 2.0 * self.M * self.V / self.n_slots


def build_interleaved_schedule(S, V, M):
    """Slot tables for ``S`` devices x ``V`` chunks x ``M`` microbatches.

    Device ``s`` owns the non-contiguous global stages ``v*S + s``; a
    microbatch therefore visits every device ``V`` times. Forwards issue
    in Megatron's chunk-major group order (chunk 0 on microbatches
    ``0..S-1``, then chunk 1 on the same group, ... then the next group
    of S), backwards mirror it; after the Megatron warmup quota
    ``2*(S-1-s) + (V-1)*S`` each device holds its in-flight forward
    count AT the quota (forward when below, backward when at/above) —
    the discrete 1F1B discipline. One list-scheduling pass resolves the
    per-slot readiness (activations/cotangents travel one ring hop per
    slot boundary); the resulting slot count hits ``2*M*V + 2*(S-1)``
    whenever ``S | M`` — bubble ``2(S-1) / (2MV + 2(S-1))``, the ~V-fold
    reduction over the non-interleaved schedule — and degrades
    gracefully (a few extra slots) on ragged ``M % S`` remainders.
    Dependency-safety and stash-ring collision-freedom are asserted at
    build time, not assumed.
    """
    if S < 1 or V < 1 or M < 1:
        raise ValueError(f"need S,V,M >= 1, got S={S} V={V} M={M}")
    total = M * V

    def warm(s):
        return min(2 * (S - 1 - s) + (V - 1) * S, total)

    fwd_q = {s: sorted(((v, m) for v in range(V) for m in range(M)),
                       key=lambda t: (t[1] // S, t[0], t[1] % S))
             for s in range(S)}
    bwd_q = {s: sorted(((v, m) for v in range(V) for m in range(M)),
                       key=lambda t: (t[1] // S, V - 1 - t[0], t[1] % S))
             for s in range(S)}
    f_slot, b_slot = {}, {}

    def f_arrival(s, v, m):
        if s == 0 and v == 0:
            return 0                      # injected from xs
        prod = f_slot.get((s - 1, v, m)) if s > 0 \
            else f_slot.get((S - 1, v - 1, m))
        return None if prod is None else prod + 1

    def b_arrival(s, v, m):
        own = f_slot.get((s, v, m))
        if own is None:
            return None                   # own forward not yet run
        if s == S - 1 and v == V - 1:
            return own + 1                # loss cotangent, made locally
        prod = b_slot.get((0, v + 1, m)) if s == S - 1 \
            else b_slot.get((s + 1, v, m))
        return None if prod is None else max(own + 1, prod + 1)

    actions, done_b, u = [], 0, 0
    limit = 4 * (total + S * V) + 16 * S + 64
    while done_b < S * total:
        if u >= limit:
            raise AssertionError(
                f"interleaved schedule deadlocked at slot {u} "
                f"(S={S} V={V} M={M})")
        row = []
        for s in range(S):
            nf = fwd_q[s][0] if fwd_q[s] else None
            nb = bwd_q[s][0] if bwd_q[s] else None
            fa = f_arrival(s, *nf) if nf else None
            ba = b_arrival(s, *nb) if nb else None
            f_ready = fa is not None and fa <= u
            b_ready = ba is not None and ba <= u
            f_done = total - len(fwd_q[s])
            in_flight = f_done - (total - len(bwd_q[s]))
            if f_done < warm(s):
                choice = "f" if f_ready else None
            elif in_flight < warm(s):
                choice = "f" if f_ready else ("b" if b_ready else None)
            else:
                choice = "b" if b_ready else ("f" if f_ready else None)
            if choice == "f":
                v, m = fwd_q[s].pop(0)
                f_slot[(s, v, m)] = u
                row.append((0, v, m))
            elif choice == "b":
                v, m = bwd_q[s].pop(0)
                b_slot[(s, v, m)] = u
                done_b += 1
                row.append((1, v, m))
            else:
                row.append((2, 0, 0))
        actions.append(row)
        u += 1

    U = len(actions)
    # Activation-stash and cotangent-buffer lifetimes per (device,
    # chunk): an activation is written when it ARRIVES (or at the
    # forward subtick for the injected stage-0/chunk-0 input) and freed
    # by the backward subtick; a cotangent is written one slot after its
    # producer (or at the local forward for the loss head) and freed by
    # the backward. Ring depth = max concurrent lifetimes, then the
    # m -> m % depth mapping is checked collision-free.
    def ring_depth(intervals_by_chunk, what):
        depth = 1
        for ivs in intervals_by_chunk.values():
            for t in range(U):
                depth = max(depth, sum(1 for (a, b, _) in ivs
                                       if a <= t <= b))
        while True:
            ok = True
            for ivs in intervals_by_chunk.values():
                for i, (a, b, m) in enumerate(ivs):
                    for (a2, b2, m2) in ivs[i + 1:]:
                        if m % depth == m2 % depth and a <= b2 and a2 <= b:
                            ok = False
            if ok:
                return depth
            depth += 1
            if depth > M:
                raise AssertionError(f"no collision-free {what} ring "
                                     f"depth <= M (S={S} V={V} M={M})")

    stash_iv, ctg_iv = {}, {}
    for (s, v, m), bs in b_slot.items():
        fs = f_slot[(s, v, m)]
        if s == 0 and v == 0:
            a_w = fs
        else:
            prod = f_slot[(s - 1, v, m)] if s > 0 \
                else f_slot[(S - 1, v - 1, m)]
            a_w = prod + 1
        stash_iv.setdefault((s, v), []).append((a_w, bs, m))
        if s == S - 1 and v == V - 1:
            c_w = fs
        else:
            prod = b_slot[(0, v + 1, m)] if s == S - 1 \
                else b_slot[(s + 1, v, m)]
            c_w = prod + 1
        ctg_iv.setdefault((s, v), []).append((c_w, bs, m))
    Q = ring_depth(stash_iv, "activation")
    Qb = ring_depth(ctg_iv, "cotangent")

    kind = np.full((U, S), 2, np.int32)
    chunk = np.zeros((U, S), np.int32)
    mb = np.zeros((U, S), np.int32)
    stash_idx = np.zeros((U, S), np.int32)
    ctg_idx = np.zeros((U, S), np.int32)
    rf_valid = np.zeros((U, S), np.int32)
    rf_idx = np.zeros((U, S), np.int32)
    rb_valid = np.zeros((U, S), np.int32)
    rb_idx = np.zeros((U, S), np.int32)
    for t, row in enumerate(actions):
        for s, (k, v, m) in enumerate(row):
            last_global = s == S - 1 and v == V - 1
            # kind 3 = forward that ALSO runs the loss head: only the
            # final global stage's forwards, known statically here, so
            # the engine's plain-forward branch never pays the
            # [mb,T,D]@[D,vocab] head matmul (which would otherwise run
            # masked on every fwd subtick — a cost scaling with the
            # very V the schedule adds to shrink the bubble).
            kind[t, s] = 3 if (k == 0 and last_global) else k
            chunk[t, s], mb[t, s] = v, m
            stash_idx[t, s] = v * Q + m % Q
            ctg_idx[t, s] = v * Qb + m % Qb
            if k == 0 and not last_global:
                # forward output travels one ring hop (s -> s+1 mod S,
                # wrapping into the next chunk off the last device)
                sc, vc = ((s + 1, v) if s < S - 1 else (0, v + 1))
                rf_valid[t + 1, sc] = 1
                rf_idx[t + 1, sc] = vc * Q + m % Q
            if k == 1 and not (s == 0 and v == 0):
                sc, vc = ((s - 1, v) if s > 0 else (S - 1, v - 1))
                rb_valid[t + 1, sc] = 1
                rb_idx[t + 1, sc] = vc * Qb + m % Qb
    # The engine's kind-3 branch accumulates loss/d_hp UNMASKED, so a
    # kind-3 entry anywhere but the final global stage would corrupt
    # gradients — make that impossible by construction.
    head_rows, head_cols = np.nonzero(kind == 3)
    assert (head_cols == S - 1).all() and len(head_rows) == M, \
        f"loss-head subticks misplaced (S={S} V={V} M={M})"
    return InterleavedSchedule(
        S=S, V=V, M=M, n_slots=U, stash_depth=Q, ctg_depth=Qb,
        kind=kind, chunk=chunk, mb=mb, stash_idx=stash_idx,
        ctg_idx=ctg_idx, rf_valid=rf_valid, rf_idx=rf_idx,
        rb_valid=rb_valid, rb_idx=rb_idx)


def _chunk_permutation(n_layers, S, V):
    """Row permutation taking the canonical stacked-layer order to the
    device-major interleaved order (device ``s`` holds global stages
    ``v*S + s`` as ``V`` contiguous blocks), and its inverse."""
    if n_layers % (S * V):
        raise ValueError(f"stacked layer axis {n_layers} must divide "
                         f"into {S} stages x {V} virtual chunks")
    lb = n_layers // (S * V)
    perm = np.concatenate([np.arange(lb) + (v * S + s) * lb
                           for s in range(S) for v in range(V)])
    return perm, np.argsort(perm)


def _interleaved_inner(stage_fn, loss_fn, sched, aux_cotangent, axis):
    """Per-device program for the interleaved schedule (the body that
    runs manual over the pipe axis). Factored out of
    :func:`interleaved_one_f_one_b` so hvdlint can trace it standalone
    and tests can execute it under ``jax.vmap(..., axis_name=axis)``.

    ``sp`` leaves carry the device's ``V`` chunk blocks stacked
    (device-major permuted, leading dim ``V * Lb``).
    """
    S, V, M = sched.S, sched.V, sched.M
    Q, Qb = sched.stash_depth, sched.ctg_depth
    tables = tuple(jnp.asarray(t) for t in
                   (sched.kind, sched.chunk, sched.mb, sched.stash_idx,
                    sched.ctg_idx, sched.rf_valid, sched.rf_idx,
                    sched.rb_valid, sched.rb_idx))

    def inner(sp, hp, xs_, largs_):
        stage = lax.axis_index(axis)
        spv = jax.tree.map(
            lambda a: a.reshape((V, a.shape[0] // V) + a.shape[1:]), sp)
        mb_shape = xs_[0]

        def chunk_params(v):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, v, 0,
                                                   keepdims=False), spv)

        def slot(state, rows):
            (fwd_c, bwd_c, stash, ctg, d_sp, d_hp, d_xs, loss,
             aux) = state
            (kind, v_a, m_a, sidx, cidx, rfv, rfi, rbv,
             rbi) = [jnp.take(r, stage) for r in rows]

            # Deliver what the carries brought at the slot boundary
            # into the per-chunk rings (garbage hops are masked out).
            cur = lax.dynamic_index_in_dim(stash, rfi, 0, keepdims=False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(rfv > 0, fwd_c, cur), rfi, 0)
            curb = lax.dynamic_index_in_dim(ctg, rbi, 0, keepdims=False)
            ctg = lax.dynamic_update_index_in_dim(
                ctg, jnp.where(rbv > 0, bwd_c, curb), rbi, 0)

            zero_mb = jnp.zeros_like(mb_shape)

            def make_fwd(with_head):
                # Two forward branches, selected by the HOST tables
                # (kind 3 = the final global stage's forwards): only
                # those pay the loss head — one linearization yields
                # the microbatch loss, its cotangent wrt the chunk
                # output, AND the head-param grads (see one_f_one_b) —
                # while every other forward subtick skips the
                # [mb,T,D]@[D,vocab] head matmul entirely.
                def do_fwd(st):
                    stash, ctg, d_sp, d_hp, d_xs, loss, aux = st
                    stored = lax.dynamic_index_in_dim(stash, sidx, 0,
                                                      keepdims=False)
                    inj = lax.dynamic_index_in_dim(xs_, m_a, 0,
                                                   keepdims=False)
                    x_in = jnp.where((stage == 0) & (v_a == 0), inj,
                                     stored)
                    # Re-stored even when it just arrived: the injected
                    # stage-0/chunk-0 input must land in the ring for
                    # the backward subtick's recompute.
                    stash = lax.dynamic_update_index_in_dim(
                        stash, x_in, sidx, 0)
                    out, a = stage_fn(chunk_params(v_a), x_in)
                    aux = aux + a
                    if with_head:
                        la = jax.tree.map(
                            lambda t: lax.dynamic_index_in_dim(
                                t, m_a, 0, keepdims=False), largs_)
                        lval, (g_last, d_hp_m) = jax.value_and_grad(
                            lambda o, h: loss_fn(h, o, la),
                            argnums=(0, 1))(out, hp)
                        loss = loss + lval
                        d_hp = jax.tree.map(jnp.add, d_hp, d_hp_m)
                        ctg = lax.dynamic_update_index_in_dim(
                            ctg, g_last, cidx, 0)
                    return (stash, ctg, d_sp, d_hp, d_xs, loss, aux,
                            out, zero_mb)
                return do_fwd

            def do_bwd(st):
                stash, ctg, d_sp, d_hp, d_xs, loss, aux = st
                x_b = lax.dynamic_index_in_dim(stash, sidx, 0,
                                               keepdims=False)
                g_in = lax.dynamic_index_in_dim(ctg, cidx, 0,
                                                keepdims=False)
                _, pull = jax.vjp(stage_fn, chunk_params(v_a), x_b)
                d_sp_v, dx = pull((g_in, jnp.float32(aux_cotangent)))
                d_sp = jax.tree.map(
                    lambda acc, g: lax.dynamic_update_index_in_dim(
                        acc,
                        lax.dynamic_index_in_dim(acc, v_a, 0,
                                                 keepdims=False) + g,
                        v_a, 0),
                    d_sp, d_sp_v)
                # Stage 0 / chunk 0's dx is the gradient wrt xs[m].
                cur = lax.dynamic_index_in_dim(d_xs, m_a, 0,
                                               keepdims=False)
                d_xs = lax.dynamic_update_index_in_dim(
                    d_xs, jnp.where((stage == 0) & (v_a == 0), dx, cur),
                    m_a, 0)
                return (stash, ctg, d_sp, d_hp, d_xs, loss, aux,
                        zero_mb, dx)

            def do_idle(st):
                return st + (zero_mb, zero_mb)

            (stash, ctg, d_sp, d_hp, d_xs, loss, aux, f_pay,
             b_pay) = lax.switch(
                kind, [make_fwd(False), do_bwd, do_idle,
                       make_fwd(True)],
                (stash, ctg, d_sp, d_hp, d_xs, loss, aux))

            fwd_c = lax.ppermute(f_pay, axis,
                                 [(i, (i + 1) % S) for i in range(S)])
            bwd_c = lax.ppermute(b_pay, axis,
                                 [(i, (i - 1) % S) for i in range(S)])
            return (fwd_c, bwd_c, stash, ctg, d_sp, d_hp, d_xs, loss,
                    aux), None

        init = (jnp.zeros_like(mb_shape), jnp.zeros_like(mb_shape),
                jnp.zeros((V * Q,) + mb_shape.shape, mb_shape.dtype),
                jnp.zeros((V * Qb,) + mb_shape.shape, mb_shape.dtype),
                jax.tree.map(jnp.zeros_like, spv),
                jax.tree.map(jnp.zeros_like, hp),
                jnp.zeros_like(xs_),
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        (_, _, _, _, d_sp, d_hp, d_xs, loss, aux), _ = lax.scan(
            slot, init, tables)

        def share(x):
            # f32 psum for sub-f32 payloads: XLA CPU's
            # AllReducePromotion pass crashes on bf16 allreduce inside
            # manual shard_map (as in gpipe/one_f_one_b).
            if x.dtype in (jnp.bfloat16, jnp.float16):
                return lax.psum(x.astype(jnp.float32),
                                axis).astype(x.dtype)
            return lax.psum(x, axis)

        d_sp = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],)
                                + a.shape[2:]), d_sp)
        d_hp = jax.tree.map(share, d_hp)
        d_xs = share(d_xs)
        loss = lax.psum(loss, axis)
        aux = lax.psum(aux, axis)
        return d_sp, d_hp, d_xs, loss, aux

    return inner


def interleaved_one_f_one_b(stage_fn, loss_fn, stage_params, head_params,
                            xs, loss_args, mesh, axis="pipe",
                            num_virtual=1, aux_cotangent=0.0):
    """Interleaved (virtual-stage) 1F1B: each device holds ``V``
    NON-contiguous model chunks (global stage ``v*S + s`` on device
    ``s``), microbatches round-robin through the ``S*V`` virtual stages,
    and every slot is a single chunk subtick — forward OR backward —
    chosen per device by the host-built :func:`build_interleaved_schedule`
    tables. Warmup fills the ``S*V``-deep virtual pipeline at full
    forward rate, the steady phase alternates 1F1B per device, and
    cooldown drains backwards, so the bubble drops to
    ``2(S-1) / (2MV + 2(S-1))`` — ~V-fold below :func:`one_f_one_b`'s
    lockstep ``2(S-1)/(M + 2(S-1))`` — at the price of ``V`` ppermute
    ring hops per microbatch instead of one, which the steady phase
    hides behind real chunk compute.

    Same contract as :func:`one_f_one_b` (``stage_fn`` now receives a
    CHUNK block — ``n_layers/(S*V)`` stacked layers; ``loss_fn`` is the
    per-microbatch objective numerator); returns ``(loss_sum, aux_sum,
    d_stage_params, d_head_params, d_xs)`` with ``d_stage_params`` in
    the CANONICAL stacked-layer order (the device-major permutation is
    applied and inverted internally — NOTE: under contiguous-block pipe
    partition rules that is a params-sized reshard in and a grads-sized
    reshard out per step; a production multi-chip deployment should
    store the stacked weights pre-permuted device-major and shard THAT
    over the pipe axis instead, see docs/benchmarks.md round 6).
    ``num_virtual=1`` degenerates to
    the TRUE non-interleaved 1F1B (single-subtick slots — bubble
    ``2(S-1)/(2M + 2(S-1))``, already below the lockstep variant).

    Reference analog: none (net-new); the schedule is the public
    interleaved 1F1B formulation (Megatron-LM's virtual pipeline).
    """
    S = mesh.shape[axis]
    V = int(num_virtual)
    M = xs.shape[0]
    sched = build_interleaved_schedule(S, V, M)
    xs, cast_dt = _cast_f32_on_cpu(mesh, xs)

    leaves = jax.tree.leaves(stage_params)
    if not leaves:
        raise ValueError("empty stage_params")
    perm, inv = _chunk_permutation(leaves[0].shape[0], S, V)
    sp_perm = jax.tree.map(lambda a: a[perm], stage_params)

    inner = _interleaved_inner(stage_fn, loss_fn, sched, aux_cotangent,
                               axis)
    d_sp, d_hp, d_xs, loss, aux = _pipe_spmd(
        inner, mesh, axis, (True, False, False, False),
        (True, False, False, False, False))(
            sp_perm, head_params, xs, loss_args)
    d_sp = jax.tree.map(lambda a: a[inv], d_sp)
    if cast_dt is not None:
        d_xs = d_xs.astype(cast_dt)
    return loss, aux, d_sp, d_hp, d_xs


# ---- static-analysis hooks (hvdlint) ---------------------------------

SCHEDULES = ("gpipe", "1f1b", "interleaved_1f1b")


def build_pipeline_inner(schedule, stage_fn, loss_fn=None, *, S, M,
                         num_virtual=1, axis="pipe", aux_cotangent=0.0):
    """Build a schedule's per-device manual program WITHOUT running it.

    This is the program-builder hook ``horovod_tpu.analysis`` (hvdlint)
    traces: the returned ``inner`` is exactly what the engines hand to
    ``_pipe_spmd``, so linting it covers the real collective sequence;
    it is traced with ``jax.make_jaxpr(axis_env=[(axis, S)])``, which
    needs neither a mesh nor devices.

    ``schedule="gpipe"`` returns ``inner(sp, xs)``; the 1F1B variants
    return ``inner(sp, hp, xs, largs)`` and require ``loss_fn``.
    """
    if schedule == "gpipe":
        return _gpipe_inner(stage_fn, S, M, axis)
    if loss_fn is None:
        raise ValueError(f"schedule {schedule!r} requires loss_fn")
    if schedule == "1f1b":
        return _one_f_one_b_inner(stage_fn, loss_fn, S, M, axis,
                                  aux_cotangent)
    if schedule == "interleaved_1f1b":
        sched = build_interleaved_schedule(S, int(num_virtual), M)
        return _interleaved_inner(stage_fn, loss_fn, sched,
                                  aux_cotangent, axis)
    raise ValueError(f"unknown schedule {schedule!r}: expected one of "
                     f"{SCHEDULES}")


def predicted_collectives(schedule, *, S, M, num_virtual=1, axis="pipe",
                          n_head_leaves=2):
    """The ordered collective sequence a schedule's inner program MUST
    emit, predicted from the host-side schedule structure — the ground
    truth for hvdlint's C5 schedule-conformance check.

    - gpipe: one activation ``ppermute`` per scan step (``M + S - 1``
      steps), then the result and aux ``psum`` pair;
    - 1f1b: one forward and one backward ``ppermute`` per lockstep slot
      (``M + 2(S-1)`` slots), then the shared-gradient ``psum`` tail;
    - interleaved_1f1b: two ``ppermute`` ring hops per slot, with the
      slot count taken from :func:`build_interleaved_schedule` — the
      SAME table the engine executes, so any engine/table drift is a
      C5 error before launch.

    ``n_head_leaves`` is the leaf count of the loss-head param tree
    (llama: final_norm + lm_head = 2); the psum tail is those leaves
    plus d_xs, loss, and aux. Returns ``[(prim_name, (axis,)), ...]``.
    """
    pp, ps = ("ppermute", (axis,)), ("psum", (axis,))
    if schedule == "gpipe":
        return [pp] * (M + S - 1) + [ps] * 2
    tail = [ps] * (n_head_leaves + 3)
    if schedule == "1f1b":
        return [pp] * (2 * (M + 2 * (S - 1))) + tail
    if schedule == "interleaved_1f1b":
        sched = build_interleaved_schedule(S, int(num_virtual), M)
        return [pp] * (2 * sched.n_slots) + tail
    raise ValueError(f"unknown schedule {schedule!r}: expected one of "
                     f"{SCHEDULES}")
