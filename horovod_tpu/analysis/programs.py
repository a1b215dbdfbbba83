"""Registry of the repo's shipped jitted programs, lint-ready.

Every program the training stack jits — the monolithic and split llama
train steps, both fused optimizer applies, and all three pipeline
schedule engines — is buildable here with abstract inputs, so the CLI
(``python -m horovod_tpu.analysis.lint --all``), ``make lint`` and
the pytest fixture all lint the SAME set.
Adding a program here is how a future subsystem buys pre-launch
collective-consistency checking for free.

Pipeline programs are linted at the per-device ``inner`` level (built
by ``parallel.pipeline.build_pipeline_inner`` from the same
``models.llama`` stage/loss programs the engines run) with the
host-schedule prediction attached — no mesh, devices, or shard_map
required.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from horovod_tpu.analysis.api import lint

# Pipeline lint geometry: S stages x V virtual chunks x M microbatches.
_S, _V, _M = 2, 2, 4
_BATCH, _SEQ = 4, 8


@dataclasses.dataclass
class LintSpec:
    """One program plus everything ``lint`` needs to analyze it."""

    fn: object
    args: tuple
    mesh: object = None
    axis_env: object = None
    expect_collectives: object = None
    donate_argnums: tuple = ()

    def run(self, allow=()):
        return lint(self.fn, self.args, mesh=self.mesh,
                    axis_env=self.axis_env,
                    donate_argnums=self.donate_argnums,
                    expect_collectives=self.expect_collectives,
                    allow=allow)


def _config(name):
    from horovod_tpu.models.llama import LlamaConfig

    # n_layers=4 so the layer stack divides into S*V=4 pipeline chunks.
    presets = {
        "tiny": lambda: LlamaConfig.tiny(n_layers=4),
        "tiny_moe": lambda: LlamaConfig.tiny_moe(n_layers=4),
    }
    if name not in presets:
        raise ValueError(f"unknown config {name!r}: expected one of "
                         f"{sorted(presets)}")
    return presets[name]()


def _abstract_params(cfg):
    from horovod_tpu.models.llama import llama_init

    return jax.eval_shape(
        lambda: llama_init(cfg, jax.random.PRNGKey(0)))


def _abstract_batch():
    tok = jax.ShapeDtypeStruct((_BATCH, _SEQ), jnp.int32)
    return {"tokens": tok, "targets": tok,
            "mask": jax.ShapeDtypeStruct((_BATCH, _SEQ), jnp.float32)}


def _mesh():
    """A trivial mesh over whatever devices exist: lint only needs the
    axis NAMES declared; every axis can be size 1."""
    from horovod_tpu.parallel.mesh import create_mesh

    return create_mesh()


def _loss_fn(cfg, mesh):
    from horovod_tpu.models.llama import llama_loss

    return functools.partial(llama_loss, config=cfg, mesh=mesh)


def _monolithic(config):
    cfg = _config(config)
    mesh = _mesh()
    loss = _loss_fn(cfg, mesh)
    step = jax.jit(lambda p, b: jax.value_and_grad(loss)(p, b))
    return LintSpec(fn=step, args=(_abstract_params(cfg),
                                   _abstract_batch()), mesh=mesh)


def _split(config, optimizer_name):
    import optax

    from horovod_tpu.parallel.precision import (
        fused_adam,
        fused_master_adam,
    )
    from horovod_tpu.parallel.train_step import make_split_train_step

    cfg = _config(config)
    mesh = _mesh()
    optimizer = {
        "adam": lambda: optax.adam(1e-3),
        "fused_adam": lambda: fused_adam(1e-3),
        "fused_master_adam": lambda: fused_master_adam(1e-3),
    }[optimizer_name]()
    ts = make_split_train_step(_loss_fn(cfg, mesh), optimizer,
                               microbatches=2)
    carry = jax.eval_shape(ts.init, _abstract_params(cfg))
    return LintSpec(fn=ts.step, args=(carry, _abstract_batch()),
                    mesh=mesh)


def _split_telemetry(config):
    """The telemetry-instrumented split step: identical jitted programs
    with a StepTimer wrapped around them. Registered so ``make lint``
    proves the host-side instrumentation never perturbs the traced
    collective signature (the StepTimer lives entirely outside jit)."""
    import optax

    from horovod_tpu.parallel.train_step import make_split_train_step
    from horovod_tpu.telemetry import StepTimer

    cfg = _config(config)
    mesh = _mesh()
    # flops preset: lint traces with abstract args, so the first-call
    # cost-analysis registration must not trigger (it lowers programs).
    timer = StepTimer(flops_per_step=1.0, block=False)
    ts = make_split_train_step(_loss_fn(cfg, mesh), optax.adam(1e-3),
                               microbatches=2, telemetry=timer)
    carry = jax.eval_shape(ts.init, _abstract_params(cfg))
    return LintSpec(fn=ts.step, args=(carry, _abstract_batch()),
                    mesh=mesh)


_ZERO_SHARDS = 4


def _split_zero(config):
    """The ZeRO-1 split step (``make_split_train_step(zero=...)``),
    traced end-to-end with its shards as a vmapped axis (a
    ``ZeroConfig`` without a mesh): proves the restructured
    step traces cleanly and that its apply program's donations (full
    params + sharded opt state) alias 1:1 (C4). The vmap
    lowers the named-axis collectives away at trace time, so the REAL
    collective signature is linted separately via
    ``zero1_shard_apply``."""
    from horovod_tpu.parallel.precision import fused_adam
    from horovod_tpu.parallel.train_step import make_split_train_step
    from horovod_tpu.parallel.zero import ZeroConfig

    cfg = _config(config)
    mesh = _mesh()
    ts = make_split_train_step(
        _loss_fn(cfg, mesh), fused_adam(1e-3), microbatches=2,
        zero=ZeroConfig(axis="data", size=_ZERO_SHARDS,
                        bucket_bytes=1 << 20))
    carry = jax.eval_shape(ts.init, _abstract_params(cfg))
    return LintSpec(fn=ts.step, args=(carry, _abstract_batch()),
                    mesh=mesh)


def _zero_shard_apply(config):
    """The per-rank ZeRO apply program at the llama geometry, traced
    with ``axis_env`` exactly like the pipeline inners — psum_scatter /
    all_gather stay visible to the walker, so C2 (axis validity), C3
    (width), and C6 (every reduce-scatter pairs with an allgather on
    the same axis) run against the program the TPU lanes execute."""
    from horovod_tpu.parallel.ops import predicted_zero_collectives
    from horovod_tpu.parallel.precision import fused_adam
    from horovod_tpu.parallel.zero import (
        ZeroAdamState,
        build_zero_apply_inner,
        zero_bucket_layout,
    )

    cfg = _config(config)
    params = _abstract_params(cfg)
    leaves, _ = jax.tree.flatten(params)
    layout = zero_bucket_layout(leaves, _ZERO_SHARDS, 1 << 20)
    inner = build_zero_apply_inner(fused_adam(1e-3).hyper, layout,
                                   "data", _ZERO_SHARDS)
    flat = tuple(jax.ShapeDtypeStruct((b.padded,), b.dtype)
                 for b in layout.buckets)
    shard = tuple(
        jax.ShapeDtypeStruct((b.shard_elems(_ZERO_SHARDS),), b.dtype)
        for b in layout.buckets)
    opt = ZeroAdamState(
        count=jax.ShapeDtypeStruct((1,), jnp.int32),
        mu=shard, nu=shard)
    return LintSpec(fn=inner, args=(flat, flat, opt),
                    axis_env=[("data", _ZERO_SHARDS)],
                    expect_collectives=predicted_zero_collectives(
                        len(layout.buckets), "data"))


_HIER_INTRA, _HIER_INTER = 2, 2


def _hier_allreduce(config):
    """The composed-plane allreduce (``parallel.ops.hier_allreduce``):
    reduce-scatter over the intra (ICI) axis, psum of the 1/L shard
    over the inter (DCN) axis, allgather back — traced with BOTH axes
    in the env so C2 validates the composed axes and C5 pins the plane
    sequence against ``predicted_hier_collectives`` (the same
    three-step table csrc's HierarchicalAllreduce executes)."""
    del config
    from horovod_tpu.parallel.ops import (
        hier_allreduce,
        predicted_hier_collectives,
    )

    def fn(x):
        return hier_allreduce(x, "intra", "inter")

    x = jax.ShapeDtypeStruct((8 * _HIER_INTRA, 4), jnp.float32)
    return LintSpec(
        fn=fn, args=(x,),
        axis_env=[("intra", _HIER_INTRA), ("inter", _HIER_INTER)],
        expect_collectives=predicted_hier_collectives("intra", "inter"))


def _zero_shard_apply_hier(config):
    """The cross-plane ZeRO apply (``ZeroConfig(inter_axis=...)``): the
    RS/AG pair rides the intra axis while the 1/N gradient shard psums
    over the inter axis between them. C6 must still see every
    reduce-scatter paired with a same-axis allgather (the interleaved
    cross-plane psum sits between, which order-based counting
    tolerates), and C2 validates both axes."""
    from horovod_tpu.parallel.ops import predicted_zero_collectives
    from horovod_tpu.parallel.precision import fused_adam
    from horovod_tpu.parallel.zero import (
        ZeroAdamState,
        build_zero_apply_inner,
        zero_bucket_layout,
    )

    cfg = _config(config)
    params = _abstract_params(cfg)
    leaves, _ = jax.tree.flatten(params)
    layout = zero_bucket_layout(leaves, _ZERO_SHARDS, 1 << 20)
    inner = build_zero_apply_inner(
        fused_adam(1e-3).hyper, layout, "data", _ZERO_SHARDS,
        inter_axis="cross", inter_size=_HIER_INTER)
    flat = tuple(jax.ShapeDtypeStruct((b.padded,), b.dtype)
                 for b in layout.buckets)
    shard = tuple(
        jax.ShapeDtypeStruct((b.shard_elems(_ZERO_SHARDS),), b.dtype)
        for b in layout.buckets)
    opt = ZeroAdamState(
        count=jax.ShapeDtypeStruct((1,), jnp.int32),
        mu=shard, nu=shard)
    return LintSpec(fn=inner, args=(flat, flat, opt),
                    axis_env=[("data", _ZERO_SHARDS),
                              ("cross", _HIER_INTER)],
                    expect_collectives=predicted_zero_collectives(
                        len(layout.buckets), "data", inter_axis="cross"))


def _zero_fused_step(config):
    """The fused one-program ZeRO-1 step AFTER
    ``parallel.fusion.interleave_collectives`` reschedules it: the
    per-member grad+apply program is traced once with ``axis_env`` (so
    the per-bucket reduce-scatter / all-gather chains stay visible),
    reordered, then replayed through ``jaxpr_as_fun`` — the lint walker
    sees exactly the equation order the jit lane hands XLA. C7 proves
    the scatters sit interleaved with the backward dot_generals rather
    than bunched at the tail, and C6 still pairs every scatter with its
    same-axis allgather."""
    from horovod_tpu.parallel.fusion import (
        _jcore,
        fused_zero_inner,
        interleave_collectives,
    )
    from horovod_tpu.parallel.precision import fused_adam
    from horovod_tpu.parallel.zero import (
        _optimizer_hyper,
        zero_bucket_layout,
        zero_state_init,
    )

    cfg = _config(config)
    params = _abstract_params(cfg)
    leaves, treedef = jax.tree.flatten(params)
    # Small buckets so the tiny config splits into MANY of them — C7's
    # interleaving verdict is only meaningful with multiple scatters
    # (one bucket has nothing to interleave with and gates the check).
    layout = zero_bucket_layout(leaves, _ZERO_SHARDS, 1 << 15)
    hyper = _optimizer_hyper(fused_adam(1e-3))
    _, opt = jax.eval_shape(
        lambda p: zero_state_init(hyper, layout, p, _ZERO_SHARDS),
        params)
    inner, example, _, env = fused_zero_inner(
        _loss_fn(cfg, None), params, _abstract_batch(), opt, hyper,
        layout, treedef, "data", _ZERO_SHARDS)
    closed = jax.make_jaxpr(inner, axis_env=env)(*example)
    fn = _jcore.jaxpr_as_fun(interleave_collectives(closed))
    return LintSpec(fn=fn, args=tuple(example), axis_env=env)


def _redistribute_to_replicated(config):
    """The registered redistribute program for the sharded->replicated
    plan: the in-graph equivalent of one allgatherv, with C5's expected
    sequence taken from the PLAN itself
    (``ReshardPlan.expected_collectives``) — a plan edit that changes
    the collective mix without this program following along (or vice
    versa) fails lint before it ships."""
    del config
    from jax import lax

    from horovod_tpu.parallel.reshard import Layout, plan_redistribute

    shards, rows = 4, 16
    plan = plan_redistribute((rows, 4), jnp.float32,
                             Layout.sharded(rows, shards),
                             Layout.replicated(shards))

    def fn(x):
        return lax.all_gather(x, "shard", axis=0, tiled=True)

    x = jax.ShapeDtypeStruct((rows // shards, 4), jnp.float32)
    return LintSpec(fn=fn, args=(x,), axis_env=[("shard", shards)],
                    expect_collectives=plan.expected_collectives("shard"))


def _pipeline(config, schedule):
    from horovod_tpu.models.llama import llama_pipeline_programs
    from horovod_tpu.parallel.pipeline import (
        build_pipeline_inner,
        predicted_collectives,
    )

    cfg = _config(config)
    stage_fn, loss_fn, aux_ct = llama_pipeline_programs(
        cfg, mesh=None, microbatches=_M, denom=float(_BATCH * _SEQ))
    inner = build_pipeline_inner(schedule, stage_fn, loss_fn, S=_S,
                                 M=_M, num_virtual=_V,
                                 aux_cotangent=aux_ct)
    expect = predicted_collectives(schedule, S=_S, M=_M,
                                   num_virtual=_V, n_head_leaves=2)

    params = _abstract_params(cfg)
    layers = params["layers"]
    # Per-device stage block: leading stacked-layer axis / S (the
    # interleaved engine holds the same total as V chunks of L/(S*V)).
    sp = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            (l.shape[0] // _S,) + l.shape[1:], l.dtype), layers)
    mb = _BATCH // _M
    d = cfg.d_model
    xs = jax.ShapeDtypeStruct((_M, mb, _SEQ, d), cfg.compute_dtype)
    if schedule == "gpipe":
        return LintSpec(fn=inner, args=(sp, xs),
                        axis_env=[("pipe", _S)],
                        expect_collectives=expect)
    hp = (params["final_norm"], params["lm_head"])
    largs = (jax.ShapeDtypeStruct((_M, mb, _SEQ), jnp.int32),
             jax.ShapeDtypeStruct((_M, mb, _SEQ), jnp.float32))
    return LintSpec(fn=inner, args=(sp, hp, xs, largs),
                    axis_env=[("pipe", _S)], expect_collectives=expect)


def _ring_attention(config):
    """The sequence-parallel exact-attention ring
    (``parallel.ring_attention``, XLA blockwise path): n-1 ppermute
    hops of the K/V shards around the ``sp`` axis with a
    rank-dependent causal mask per step. Exercises the walkers C8
    leans on — rank-tainted VALUES (``lax.axis_index`` feeds the mask)
    inside rank-INVARIANT control flow must stay quiet."""
    del config
    from horovod_tpu.parallel.ring_attention import ring_attention

    def fn(q, k, v):
        return ring_attention(q, k, v, "sp", causal=True, use_flash=False)

    # GQA geometry: 4 query heads over 2 KV heads, bf16 activations.
    q = jax.ShapeDtypeStruct((2, 8, 4, 8), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8, 2, 8), jnp.bfloat16)
    return LintSpec(fn=fn, args=(q, kv, kv), axis_env=[("sp", 2)])


_REGISTRY = {
    "llama_train_step": _monolithic,
    "llama_train_step_split":
        functools.partial(_split, optimizer_name="adam"),
    "llama_train_step_split_fused_adam":
        functools.partial(_split, optimizer_name="fused_adam"),
    "llama_train_step_split_fused_master_adam":
        functools.partial(_split, optimizer_name="fused_master_adam"),
    "llama_train_step_split_telemetry": _split_telemetry,
    "llama_train_step_split_zero1": _split_zero,
    "zero1_shard_apply": _zero_shard_apply,
    "zero1_shard_apply_hier": _zero_shard_apply_hier,
    "zero1_fused_step": _zero_fused_step,
    "hier_allreduce": _hier_allreduce,
    "redistribute_to_replicated": _redistribute_to_replicated,
    "pipeline_gpipe":
        functools.partial(_pipeline, schedule="gpipe"),
    "pipeline_1f1b":
        functools.partial(_pipeline, schedule="1f1b"),
    "pipeline_interleaved_1f1b":
        functools.partial(_pipeline, schedule="interleaved_1f1b"),
    "ring_attention_sp": _ring_attention,
}


def program_names():
    return sorted(_REGISTRY)


def build_program(name, config="tiny"):
    """Build a registered program's :class:`LintSpec`."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown program {name!r}: expected one of "
                         f"{program_names()}")
    return _REGISTRY[name](config)


def lint_program(name, config="tiny", allow=()):
    """Build and lint one registered program."""
    return build_program(name, config).run(allow=allow)


def lint_all(config="tiny", allow=()):
    """Lint every registered program; returns ``{name: [Diagnostic]}``."""
    return {name: lint_program(name, config, allow)
            for name in program_names()}
