"""Split-program training step: grad and optimizer-apply as two jits.

Why two programs instead of one fused train-step jit:

1. the builders measured the split layout faster at flagship shape in
   round 5 (the fused program's interleaved adam update scheduled
   worse); that chip and compiler are gone and the comparison has not
   been repeated on jax 0.9.0 — not measured;
2. each program compiles on its own (the flagship grad program takes
   35–37 s on the v5e, PERF.md; the apply program a tenth of that),
   and the eager-Horovod lane reuses the very same grad program
   around its allreduce;
3. N-way microbatch gradient accumulation falls out naturally: the
   grad program runs once per microbatch into a donated accumulator,
   so per-microbatch ACTIVATION memory is 1/N of the full batch. The
   accumulate program, though, holds params, the accumulator and a
   fresh gradient tree at once: where the gradient tree is as large as
   the activations saved (the 1.4B flagship at 4 x 2048: 2-way
   accumulation ran at 570 vs 552 ms/step on the v5e and, by the
   compiler's account, saves no memory — PERF.md) it buys nothing.

The two programs are connected by DONATED gradient buffers: the first
microbatch's gradient outputs become the accumulator and each
accumulation step donates it forward, so exactly one params-sized
gradient tree is live per step. Where the device has no room for the
gradients of a second step beside the first's (``holds_two_gradients``),
the single-microbatch grad program takes the buffers the apply has just
read as a donated argument and writes into them (``grad_program``): one
set of gradient buffers goes round, and a step can be enqueued ahead.
The apply program donates only params and optimizer state — its outputs are exactly one params tree plus one
state tree, which those donate into 1:1, so a donated gradient tree
could never alias an output and only produced XLA's "donated buffers
were not usable" warning (see apply_fn below).

Semantics: the per-microbatch loss is scaled by 1/N inside the grad
program, so the accumulated gradients equal the full-batch mean-loss
gradients and the accumulated loss equals the full-batch mean loss —
bit-for-bit-ish equivalence with the monolithic jit is pinned by
``tests/single/test_llama.py`` and the driver's ``dryrun_multichip``
split-step pass. That identity requires the loss to be a per-example
MEAN (linear in the batch axis). Batch-NONLINEAR terms become the
mean of per-microbatch values instead of the full-batch value:

- the MoE Switch aux loss (batch routing statistics) — the same
  semantics the pipeline microbatch path already has (see
  ``test_pipeline_with_moe``);
- a MASKED mean ``sum(nll*mask)/sum(mask)`` whose token counts differ
  across microbatches: each microbatch's masked mean gets weight 1/N
  regardless of how many real tokens it holds. For exact equivalence
  on padded batches, fold a GLOBAL denominator into ``loss_fn``
  (compute ``sum(mask)`` over the full batch outside the step and
  have ``loss_fn`` return ``sum(nll*mask)/global_denom``) — exactly
  what the 1F1B schedule does with its loss numerator
  (``models/llama.py``, "mask denominator is global across
  microbatches").

Reference analog: ``backward_passes_per_step`` local gradient
aggregation (``horovod/tensorflow/gradient_aggregation.py``), re-founded
as a program-structure choice instead of an optimizer wrapper.
"""

import functools
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from horovod_tpu.utils.spans import (
    files_itself,
    scope,
    span,
    step_begins,
    step_returns,
)


class TrainStep(NamedTuple):
    init: Any   # init(params) -> carry
    step: Any   # step(carry, batch) -> (loss, carry)


def _split_microbatches(batch, n):
    """Split every leaf of ``batch`` into ``n`` equal chunks along the
    leading (batch) axis. Runs OUTSIDE jit — each chunk is then a
    separate call to the grad program."""
    leaves = jax.tree.leaves(batch)
    if not leaves:
        raise ValueError("empty batch")
    b = leaves[0].shape[0]
    if b % n:
        raise ValueError(f"batch size {b} must divide into "
                         f"{n} microbatches")
    mb = b // n
    return [jax.tree.map(lambda x: x[i * mb:(i + 1) * mb], batch)
            for i in range(n)]


def holds_two_gradients(params, opt):
    """Whether the device that holds ``params`` has room for a SECOND
    set of gradients beside the state of a step: parameters, optimizer
    state, the gradients being applied, the gradients of the step
    enqueued behind them, and TWO gradients' worth of temporaries (a
    grad program's temporaries are not known before it is compiled; the
    cells' read 1.2 gradients' worth, Mistral's 2.75 GB, to 1.7, 4.67 GB
    beside 1.38 B parameters of one-part layers, which counted as one
    filled the chip to 16.89 GB and made every enqueue wait for the
    apply: PERF.md section 6, PR 50). The count is a guess from sizes
    and cannot see the batch, so a twentieth of the device is kept for
    what it misses: at 32,768 tokens a sequence the temporaries read
    2.7 gradients' worth (6.43 GB beside 1.18 B parameters), the count
    came to 98.1% of the device, the chip filled to 16.79 of 16.91 GB,
    every enqueue waited and one run in eight held steps of twice the
    time. The twentieth is a margin, not a measurement: it stands
    between that cell and the nearest one that does hold two sets
    (94.5%, fills 16.48 GB, the same program on either side of this
    change: PERF.md section 6, PR 55), and what would replace the guess
    is the compiled grad program's own ``memory_analysis`` (PERF.md
    section 7: it costs a second compile where the guess was wrong, and
    the adapters that ask this function have to be told the answer).
    Read
    off the device's own account (``memory_stats()["bytes_limit"]``); a
    device that gives none (the CPU), or parameters that are being
    traced, hold whatever is asked of them."""
    leaves = jax.tree.leaves(params)
    if not leaves or any(isinstance(x, jax.core.Tracer) for x in leaves):
        return True
    stats = next(iter(leaves[0].devices())).memory_stats() or {}
    if "bytes_limit" not in stats:
        return True
    def size(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    return size(params) * 5 + size(opt) <= 0.95 * stats["bytes_limit"]


# Gradient buffers that nothing reads any more, by what they hold (tree
# and leaves' shapes and dtypes): what the recycled grad program of the
# next step writes into. One set a model goes round through here, and a
# second step object of the same model in the process (a benchmark's
# check of the step it timed) takes the set the first one left.
_SPARE_GRADIENTS = {}


def _held(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple((x.shape, x.dtype) for x in leaves)


def spare_gradients(params):
    """Gradient buffers for ``params`` to hand to the recycled grad
    program (which consumes them): the set the last step left, else
    zeros."""
    spare = _SPARE_GRADIENTS.pop(_held(params), None)
    if spare is None or any(x.is_deleted() for x in jax.tree.leaves(spare)):
        spare = jax.tree.map(jnp.zeros_like, params)
    return spare


def leave_gradients(grads):
    """``grads`` have been handed to the apply program: the next
    recycled grad program may write into them."""
    _SPARE_GRADIENTS[_held(grads)] = grads


def drop_spare_gradients():
    """Free the gradient buffers left for a next step that will not
    come (a process that goes on to other work on a full device)."""
    _SPARE_GRADIENTS.clear()


def grad_program(loss_fn, recycled, jit_kwargs):
    """The split step's grad program, ``jit_hvd_grad`` in a device
    trace: ``(params, batch) -> (loss, grads)``. ``recycled``: it takes
    a third argument, the gradient buffers the apply program has just
    read, DONATED, and writes its gradients into them, so that
    enqueuing a step allocates no second set of gradients (the argument
    is read by nothing: ``keep_unused`` keeps it for the aliasing)."""
    if recycled:
        def hvd_grad(p, d, spare):
            return jax.value_and_grad(loss_fn)(p, d)

        return jax.jit(hvd_grad, donate_argnums=(2,), keep_unused=True,
                       **jit_kwargs)

    def hvd_grad(p, d):
        return jax.value_and_grad(loss_fn)(p, d)

    return jax.jit(hvd_grad, **jit_kwargs)


def _register_split_flops(timer, programs):
    """Fill ``timer.flops_per_step`` from compiled cost analysis:
    ``programs`` is ``[(jitted_fn, abstract_args, calls_per_step)]``.
    Uses the AOT lower/compile path with the SAME abstract signatures
    the step dispatches, so the executables land in (or come from) the
    jit cache that first step populates."""
    for fn, args, calls in programs:
        compiled = fn.lower(*args).compile()
        timer.add_flops_from_compiled(compiled, calls=calls)


def _wrap_step_telemetry(inner_step, telemetry, flops_programs):
    """The StepTimer wrapper both step layouts share: first call
    registers per-step FLOPs from compiled cost analysis (best-effort),
    every call brackets the step with ``start_step``/``end_step``. Lives
    entirely OUTSIDE the jitted programs — traced jaxprs are identical
    with and without it."""
    flops_pending = [telemetry.flops_per_step is None]

    def step(carry, batch):
        if flops_pending[0]:
            flops_pending[0] = False
            try:
                _register_split_flops(telemetry,
                                      flops_programs(carry, batch))
            except Exception:  # noqa: BLE001 — cost analysis is
                pass           # best-effort (backend-dependent)
        telemetry.start_step()
        out = inner_step(carry, batch)
        telemetry.end_step(out)
        return out

    return step


def _spanned(dispatch):
    """``step`` inside the program span ``hvd.spmd.step``: the host's
    dispatch of one step's programs, on the profiler's clock when a
    trace is being taken (docs/metrics.md "Program spans"), and
    counted where it begins and where it returns (``spans.steps_begun``:
    the compile log's ``at_step``)."""
    def step(carry, batch):
        step_begins()
        try:
            with span("hvd.spmd.step"):
                return dispatch(carry, batch)
        finally:
            step_returns()

    return step


def _make_fused_zero_train_step(loss_fn, optimizer, zero, *, n, jk,
                                telemetry):
    """The fused (one-program) ZeRO-1 step layout (docs/fusion.md).

    ``n == 1``: the whole step — value_and_grad + bucket pack + the
    per-bucket RS/adam/AG pipeline — is one jit whose collective chains
    :func:`~horovod_tpu.parallel.fusion.interleave_collectives`
    rescheduled under the backward. ``n > 1``: microbatches ``0..n-2``
    run the plain grad/accumulate programs (no collectives to fuse);
    the LAST microbatch, which owns the collective phase, runs fused
    with the accumulator folded in. The carry is identical to the
    unfused zero layout (``zero_state_init``), so the
    ``HOROVOD_JIT_FUSION`` knob flips without state conversion.
    """
    from horovod_tpu.parallel.fusion import make_fused_zero_programs

    progs = make_fused_zero_programs(loss_fn, optimizer, zero,
                                     microbatches=n, jit_kwargs=jk)

    if n == 1:
        def step(carry, batch):
            params, opt = carry
            loss, params, opt = progs.call(params, batch, opt)
            return loss, (params, opt)
    else:
        def scaled_loss(p, d):
            return loss_fn(p, d) / n

        grad_first = jax.jit(
            lambda p, d: jax.value_and_grad(scaled_loss)(p, d), **jk)

        @functools.partial(jax.jit, donate_argnums=(1, 2), **jk)
        def grad_acc(params, loss_acc, acc, d):
            loss, g = jax.value_and_grad(scaled_loss)(params, d)
            return loss_acc + loss, jax.tree.map(jnp.add, acc, g)

        def step(carry, batch):
            params, opt = carry
            mbs = _split_microbatches(batch, n)
            loss, grads = grad_first(params, mbs[0])
            for mb in mbs[1:-1]:
                loss, grads = grad_acc(params, loss, grads, mb)
            loss, params, opt = progs.call_final(params, loss, grads,
                                                 mbs[-1], opt)
            return loss, (params, opt)

    step = _spanned(step)
    if telemetry is not None:
        def _flops_programs(carry, batch):
            params, opt = carry
            if n == 1:
                fused = progs.get(params, batch, opt, False)
                return [(fused, (params, batch, opt), 1)]
            mbs = _split_microbatches(batch, n)
            l_abs, g_abs = jax.eval_shape(grad_first, params, mbs[0])
            fused = progs.get(params, mbs[-1], opt, True)
            return [(grad_first, (params, mbs[0]), 1),
                    (grad_acc, (params, l_abs, g_abs, mbs[0]), n - 2),
                    (fused, (params, l_abs, g_abs, mbs[-1], opt), 1)]

        step = _wrap_step_telemetry(step, telemetry, _flops_programs)

    return TrainStep(init=progs.init, step=step)


def make_split_train_step(loss_fn, optimizer, *, microbatches=1,
                          jit_kwargs=None, telemetry=None, zero=None):
    """Build the split-program step for ``loss_fn(params, batch)``.

    ``optimizer`` is either an optax ``GradientTransformation``
    (``init``/``update`` — the SPLIT apply: updates tree +
    ``optax.apply_updates``) or a ``FusedOptimizer`` /
    ``FusedMasterOptimizer`` from ``parallel.precision``
    (``init``/``apply`` — the single-pass FUSED apply). For the master
    variant the carry's params are the COMPUTE-dtype cast (built by
    ``init``); the fp32 master lives inside the optimizer state.

    ``zero`` (optional) is a :class:`horovod_tpu.parallel.zero.
    ZeroConfig`: the apply program is then the ZeRO-1 sharded form —
    gradient buckets reduce-scattered over ``zero.axis`` so each rank
    updates 1/N of the (fused adam / fused master-adam) optimizer
    state, updated parameter shards allgathered back — cutting
    per-rank optimizer memory N-fold at the same step semantics
    (docs/zero.md; parity pinned by tests/single/test_zero.py). The
    grad/accumulation programs are unchanged: ZeRO-1 restructures only
    the optimizer phase.

    ``telemetry`` (optional) is a
    :class:`horovod_tpu.telemetry.StepTimer`: every ``step`` call is
    then timed into it, and — unless the timer already carries
    ``flops_per_step`` — the first call registers per-step FLOPs from
    ``lowered.compile().cost_analysis()`` over the grad program(s)
    x microbatches plus the apply program, so ``timer.mfu()`` works
    with zero extra bookkeeping. The wrapper lives entirely OUTSIDE
    the jitted programs: traced jaxprs (and therefore hvdlint results
    — see ``analysis/programs.py``'s instrumented registration) are
    identical with and without it.

    Returns ``TrainStep(init, step)`` with
    ``init(params) -> carry`` and ``step(carry, batch) -> (loss,
    carry)``; ``jit_kwargs`` (e.g. TPU compiler options) apply to every
    program.
    """
    jk = dict(jit_kwargs or {})
    n = int(microbatches)
    if n < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    fused = hasattr(optimizer, "apply")

    # Grads are NOT donated: the apply program's outputs are exactly
    # one params tree + one optimizer-state tree, and params/opt donate
    # into them 1:1; a donated grads tree can never find an output to
    # alias and only triggers XLA's "Some donated buffers were not
    # usable" warning on every leaf (observed on the fp32-master path,
    # BENCH r5 tail — the r6 fix, pinned by
    # tests/single/test_llama.py::test_apply_jit_emits_no_donation_warning).
    # The buffers are dead the moment apply returns either way.
    zero_init = None
    if zero is not None:
        from horovod_tpu.parallel import fusion as _fusion

        if _fusion.jit_fusion_enabled():
            # Jit-lane compute/collective fusion (docs/fusion.md): the
            # grad program that owns the collective phase and the ZeRO
            # apply become ONE program whose per-bucket reduce-scatter
            # -> shard-adam -> all-gather chains are rescheduled to
            # interleave with the remaining backward compute
            # (hvdlint C7 verifies the ordering statically). Same math,
            # same carry, different schedule — HOROVOD_JIT_FUSION=0
            # restores the unfused two-program layout below.
            return _make_fused_zero_train_step(
                loss_fn, optimizer, zero, n=n, jk=jk,
                telemetry=telemetry)
        from horovod_tpu.parallel.zero import make_zero_apply

        apply_fn, zero_init = make_zero_apply(optimizer, zero,
                                              jit_kwargs=jk)
    else:
        if fused:
            @scope("hvd.apply")
            def hvd_apply(grads, params, opt):
                return optimizer.apply(params, grads, opt)
        else:
            @scope("hvd.apply")
            def hvd_apply(grads, params, opt):
                import optax  # deferred: parallel/ imports without optax

                updates, opt = optimizer.update(grads, opt, params)
                return optax.apply_updates(params, updates), opt

        apply_fn = jax.jit(hvd_apply, donate_argnums=(1, 2), **jk)

    # The jitted functions are named for what they are: a device trace
    # shows jit_hvd_grad and jit_hvd_apply, and a reader finds them so.
    # The step reaches them through ``run``, where each files itself at
    # its first call (``spans.scope_tables``) and stands bare after it.
    run = types.SimpleNamespace(apply=apply_fn)
    if zero is None:   # the ZeRO apply is no one program
        files_itself(run, "apply", apply_fn)
    if n == 1:
        grad_fn = grad_program(loss_fn, False, jk)
        files_itself(run, "grad", grad_fn)
        run.recycled = None    # decided at the first step

        def decide(params, opt):
            """The first step's, once: a state that fills the device
            (1.6 B parameters at 8 B each on 16 GB). The step enqueued
            behind this one could not allocate its gradients while these
            are alive, so every enqueue would wait for the apply, the
            host on the device's critical path; one set of buffers goes
            round instead (PERF.md section 6, PR 47)."""
            run.recycled = not holds_two_gradients(params, opt)
            if run.recycled:
                files_itself(run, "grad", grad_program(loss_fn, True, jk))

        def step(carry, batch):
            params, opt = carry
            if run.recycled is None:
                decide(params, opt)
            if run.recycled:
                loss, grads = run.grad(params, batch,
                                       spare_gradients(params))
                params, opt = run.apply(grads, params, opt)
                leave_gradients(grads)
                return loss, (params, opt)
            loss, grads = run.grad(params, batch)
            params, opt = run.apply(grads, params, opt)
            return loss, (params, opt)
    else:
        def scaled_loss(p, d):
            # 1/N inside the grad program: accumulated grads == the
            # full-batch mean-loss grads, accumulated loss == the
            # full-batch mean loss — no extra scaling pass anywhere.
            return loss_fn(p, d) / n

        # TWO grad programs on purpose: the first microbatch runs an
        # accumulator-free jit and its outputs BECOME the accumulator.
        # Folding both into one program by seeding grad_acc with a
        # zeros tree (halving the dominant fwd+bwd compile) was tried
        # in r7 and MISCOMPILES: with a zeros accumulator whose
        # sharding is the params', GSPMD picks a different partitioning
        # for the embedding-gradient scatter-add inside pipeline-
        # schedule programs and produces wrong embed grads on the CPU
        # substrate (loss right, one leaf off by O(grad) — caught by
        # test_interleaved_composes_with_split_train_step). Keep the
        # two-program layout unless that equivalence test passes with
        # the fold on every substrate.
        def hvd_grad(p, d):
            return jax.value_and_grad(scaled_loss)(p, d)

        def hvd_grad_acc(params, loss_acc, acc, d):
            loss, g = jax.value_and_grad(scaled_loss)(params, d)
            return loss_acc + loss, jax.tree.map(jnp.add, acc, g)

        grad_first = jax.jit(hvd_grad, **jk)
        grad_acc = jax.jit(hvd_grad_acc, donate_argnums=(1, 2), **jk)
        files_itself(run, "grad", grad_first)
        files_itself(run, "grad_acc", grad_acc)

        def step(carry, batch):
            params, opt = carry
            mbs = _split_microbatches(batch, n)
            loss, grads = run.grad(params, mbs[0])
            for mb in mbs[1:]:
                loss, grads = run.grad_acc(params, loss, grads, mb)
            params, opt = run.apply(grads, params, opt)
            return loss, (params, opt)

    step = _spanned(step)
    if telemetry is not None:
        def _flops_programs(carry, batch):
            params, opt = carry
            if n == 1:
                g_abs = jax.eval_shape(grad_fn, params, batch)
                if holds_two_gradients(params, opt):
                    grad = (grad_fn, (params, batch), 1)
                else:
                    grad = (grad_program(loss_fn, True, jk),
                            (params, batch, g_abs[1]), 1)
                return [grad, (apply_fn, (g_abs[1], params, opt), 1)]
            mb0 = _split_microbatches(batch, n)[0]
            l_abs, g_abs = jax.eval_shape(grad_first, params, mb0)
            return [(grad_first, (params, mb0), 1),
                    (grad_acc, (params, l_abs, g_abs, mb0), n - 1),
                    (apply_fn, (g_abs, params, opt), 1)]

        step = _wrap_step_telemetry(step, telemetry, _flops_programs)

    def init(params):
        if zero_init is not None:
            # ZeRO-1 carry: replicated params (compute cast for the
            # master variant), optimizer state sharded over zero.axis.
            return zero_init(params)
        opt = optimizer.init(params)
        if hasattr(optimizer, "compute_params"):
            # Master-weights variant: the carry holds the compute cast;
            # the fp32 master (inside ``opt``) owns the precision.
            params = optimizer.compute_params(opt)
        return (params, opt)

    return TrainStep(init=init, step=step)
