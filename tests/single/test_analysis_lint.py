"""hvdlint (horovod_tpu/analysis): seeded-bug detection + shipped-
program cleanliness.

Four deliberately-broken programs — one per static check class the
last rounds' bugs motivated — must each fire the EXACT diagnostic
(id + location); every shipped train-step/pipeline/optimizer
combination must lint clean. The whole suite runs on jaxpr tracing
with ``axis_env`` only: no shard_map, no multi-device mesh
(``test_full_suite_without_shard_map`` pins that).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from horovod_tpu import analysis
from horovod_tpu.analysis import programs

pytestmark = pytest.mark.quick

_ENV = [("data", 2), ("pipe", 2)]


# ---- seeded bugs: each must fire its exact diagnostic ----------------

def test_c1_cond_branches_with_divergent_collectives():
    def prog(x):
        return lax.cond(x.sum() > 0,
                        lambda y: lax.psum(y, "data"),
                        lambda y: y * 2.0, x)

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C1"]
    assert diags[0].severity == analysis.ERROR
    assert "cond" in diags[0].path
    assert "test_analysis_lint" in diags[0].source


def test_c1_rank_dependent_switch_is_called_out():
    """A switch predicate derived from lax.axis_index GUARANTEES ranks
    take different branches — the diagnostic must say so."""
    def prog(x):
        return lax.switch(lax.axis_index("data") % 2,
                          [lambda y: lax.psum(y, "data"),
                           lambda y: y], x)

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C1"]
    assert "axis_index" in diags[0].message


def test_c2_psum_over_undeclared_axis():
    def prog(x):
        return lax.psum(x, "rank")  # not a mesh axis

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C2"]
    assert "rank" in diags[0].message
    # Auto-binding the unknown axis keeps the real trace location.
    assert "test_analysis_lint" in diags[0].source


def test_c2_fires_with_no_declared_axes_at_all():
    """A collective over a typo'd axis in a program linted WITHOUT any
    mesh/axis_env must still flag C2 (the auto-bound undeclared name is
    ground truth enough); only a program with no collective axes at all
    skips the check."""
    d = analysis.lint(lambda x: lax.psum(x, "typo_axis"),
                      (jnp.ones(4),))
    assert [x.id for x in d] == ["C2"]
    assert analysis.lint(lambda x: x * 2.0, (jnp.ones(4),)) == []


def test_c1_taint_survives_scan_outputs():
    """Rank taint must propagate through loop outputs: a switch
    predicate accumulated from lax.axis_index inside a scan is still a
    GUARANTEED divergence."""
    def prog(x):
        def step(c, _):
            return c + lax.axis_index("data"), None
        acc, _ = lax.scan(step, jnp.int32(0), jnp.arange(3))
        return lax.switch(acc % 2,
                          [lambda y: lax.psum(y, "data"),
                           lambda y: y], x)

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C1"]
    assert "axis_index" in diags[0].message


def test_c3_fp32_allreduce_of_bf16():
    def prog(x):
        return lax.psum(x.astype(jnp.float32), "data")  # stays f32

    diags = analysis.lint(prog, (jnp.ones(64, jnp.bfloat16),),
                          axis_env=_ENV)
    assert [d.id for d in diags] == ["C3"]
    assert diags[0].severity == analysis.WARNING
    assert "bfloat16" in diags[0].message


def test_c3_exempts_f32_accumulate_roundtrip():
    """bf16 -> f32 -> psum -> bf16 is the recommended accumulate
    pattern (and what the pipeline share() does) — NOT a finding."""
    def prog(x):
        return lax.psum(x.astype(jnp.float32),
                        "data").astype(jnp.bfloat16)

    assert analysis.lint(prog, (jnp.ones(64, jnp.bfloat16),),
                         axis_env=_ENV) == []


def test_c4_apply_jit_donating_unusable_buffer():
    """The r6/r7 bug class: grads donated into an apply program whose
    outputs are exactly params+opt — the donated grads can never alias
    an output ('donated buffers were not usable')."""
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply_fn(grads, params, opt):
        return params - 0.1 * grads, opt + 1.0

    diags = analysis.lint(apply_fn, (jnp.ones(8),) * 3)
    assert [d.id for d in diags] == ["C4"]
    assert diags[0].path == "pjit:apply_fn"
    assert "cannot alias any output" in diags[0].message


def test_c4_clean_when_only_params_and_opt_donated():
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply_fn(grads, params, opt):
        return params - 0.1 * grads, opt + 1.0

    assert analysis.lint(apply_fn, (jnp.ones(8),) * 3) == []


def test_c5_schedule_sequence_mismatch():
    """An engine emitting one more ring hop than its host schedule
    table predicts must be a C5 error."""
    def prog(x):
        def step(c, _):
            return lax.ppermute(c, "pipe", [(0, 1), (1, 0)]), None
        c, _ = lax.scan(step, x, jnp.arange(4))  # 4 hops...
        return lax.psum(c, "pipe")

    expect = [("ppermute", ("pipe",))] * 3 + [("psum", ("pipe",))]
    diags = analysis.lint(prog, (jnp.ones(4),),
                          axis_env=[("pipe", 2)],
                          expect_collectives=expect)
    assert [d.id for d in diags] == ["C5"]
    assert "deviates" in diags[0].message


def test_c6_unpaired_reduce_scatter():
    """The ZeRO invariant (docs/zero.md): a reduce-scatter with no
    allgather on the same axis leaves state silently sharded — C6."""
    def prog(x):
        return lax.psum_scatter(x, "data", scatter_dimension=0,
                                tiled=True)

    diags = analysis.lint(prog, (jnp.ones(8),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C6"]
    assert diags[0].severity == analysis.ERROR
    assert "unpaired" in diags[0].message


def test_c6_clean_when_scatter_pairs_with_gather():
    """The ZeRO apply shape — scatter grads, update shards, gather
    params — is exactly paired and must NOT fire; a gather on a
    DIFFERENT axis does not count as the pair."""
    def paired(x):
        s = lax.psum_scatter(x, "data", scatter_dimension=0, tiled=True)
        return lax.all_gather(s - 0.1 * s, "data", axis=0, tiled=True)

    assert analysis.lint(paired, (jnp.ones(8),), axis_env=_ENV) == []

    def cross_axis(x):
        s = lax.psum_scatter(x, "data", scatter_dimension=0, tiled=True)
        return lax.all_gather(s, "pipe", axis=0, tiled=True)

    diags = analysis.lint(cross_axis, (jnp.ones(8),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C6"]


def test_c6_gather_before_scatter_does_not_mask():
    """Pairing is ORDERED: an FSDP-style param gather BEFORE the
    scatter cannot reassemble the scatter's result, so a trailing
    unpaired scatter must still fire (pure per-axis counting would be
    blind to exactly this shape)."""
    def prog(x):
        g = lax.all_gather(x, "data", axis=0, tiled=True)
        return lax.psum_scatter(g, "data", scatter_dimension=0,
                                tiled=True)

    diags = analysis.lint(prog, (jnp.ones(8),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C6"]
    assert "unpaired" in diags[0].message


def test_c6_counts_through_loops():
    """Trip counts weigh in: K scatters inside a scan against one
    gather outside is K-1 unpaired."""
    def prog(x):
        def step(c, _):
            return lax.psum_scatter(c, "data", scatter_dimension=0,
                                    tiled=True).repeat(2), None
        c, _ = lax.scan(step, x, jnp.arange(3))
        return lax.all_gather(c[:4], "data", axis=0, tiled=True)

    diags = analysis.lint(prog, (jnp.ones(8),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C6"]
    assert "3 reduce-scatter(s)" in diags[0].message
    assert "only 1 subsequent allgather(s)" in diags[0].message


def _bunched(x, w):
    """Backward-shaped fixture: ALL the arithmetic, then every bucket's
    reduce-scatter bunched at the tail — the pre-fusion split-step
    schedule C7 exists to reject."""
    a = x @ w
    b = jnp.tanh(a) @ w
    s1 = lax.psum_scatter(a.reshape(-1), "data", scatter_dimension=0,
                          tiled=True)
    s2 = lax.psum_scatter(b.reshape(-1), "data", scatter_dimension=0,
                          tiled=True)
    ga = lax.all_gather(s1, "data", axis=0, tiled=True)
    gb = lax.all_gather(s2, "data", axis=0, tiled=True)
    return ga, gb


def test_c7_tail_bunched_scatters_fire():
    x, w = jnp.ones((8, 8)), jnp.ones((8, 8))
    diags = analysis.lint(_bunched, (x, w), axis_env=_ENV)
    assert [d.id for d in diags] == ["C7"]
    assert diags[0].severity == analysis.ERROR
    assert "bunched" in diags[0].message
    assert "HOROVOD_JIT_FUSION" in diags[0].hint


def test_c7_quiet_on_interleaved_schedule():
    """The SAME collectives interleaved with the compute — each
    scatter issued the moment its operand is ready — must pass.
    ``parallel.fusion.interleave_collectives`` produces exactly this
    shape from the bunched one (pinned end-to-end by the registered
    ``zero1_fused_step`` program staying clean)."""
    def interleaved(x, w):
        a = x @ w
        s1 = lax.psum_scatter(a.reshape(-1), "data",
                              scatter_dimension=0, tiled=True)
        b = jnp.tanh(a) @ w
        s2 = lax.psum_scatter(b.reshape(-1), "data",
                              scatter_dimension=0, tiled=True)
        ga = lax.all_gather(s1, "data", axis=0, tiled=True)
        gb = lax.all_gather(s2, "data", axis=0, tiled=True)
        return ga, gb

    x, w = jnp.ones((8, 8)), jnp.ones((8, 8))
    assert analysis.lint(interleaved, (x, w), axis_env=_ENV) == []


def test_c7_quiet_on_reorder_pass_output():
    """Feeding the bunched fixture through the actual fusion pass must
    flip its verdict: the reordered jaxpr replayed via jaxpr_as_fun
    lints clean while the original fires.  Operands are 16x16 (> the
    pass's 64-element hoist threshold) so the dots count as real
    compute to weave the scatters into."""
    from horovod_tpu.parallel.fusion import (
        _jcore,
        interleave_collectives,
    )

    x, w = jnp.ones((16, 16)), jnp.ones((16, 16))
    closed = jax.make_jaxpr(_bunched, axis_env=[("data", 2)])(x, w)
    fixed = _jcore.jaxpr_as_fun(interleave_collectives(closed))
    assert analysis.lint(fixed, (x, w), axis_env=_ENV) == []


def test_c7_quiet_on_eager_lane_and_single_bucket():
    """No collectives in the jaxpr (the eager lane moves bytes outside
    jit) -> quiet; a single scatter (one bucket cannot interleave with
    itself) -> quiet; a pure-wire program (no flop mass) -> quiet."""
    def eager_shaped(x, w):
        return jnp.tanh(x @ w) @ w

    def single(x, w):
        a = jnp.tanh(x @ w) @ w
        s = lax.psum_scatter(a.reshape(-1), "data",
                             scatter_dimension=0, tiled=True)
        return lax.all_gather(s, "data", axis=0, tiled=True)

    def pure_wire(x):
        s1 = lax.psum_scatter(x, "data", scatter_dimension=0,
                              tiled=True)
        g1 = lax.all_gather(s1, "data", axis=0, tiled=True)
        s2 = lax.psum_scatter(g1, "data", scatter_dimension=0,
                              tiled=True)
        return lax.all_gather(s2, "data", axis=0, tiled=True)

    x, w = jnp.ones((8, 8)), jnp.ones((8, 8))
    assert analysis.lint(eager_shaped, (x, w), axis_env=_ENV) == []
    assert analysis.lint(single, (x, w), axis_env=_ENV) == []
    assert analysis.lint(pure_wire, (jnp.ones(8),), axis_env=_ENV) == []


def test_c8_collective_in_rank_dependent_while_fires():
    """A psum inside a while_loop whose trip count derives from
    lax.axis_index is a GUARANTEED deadlock: ranks exit the loop after
    different iteration counts, so collective call counts diverge."""
    def prog(x):
        def cond(c):
            i, _ = c
            return i < lax.axis_index("data") + 1

        def body(c):
            i, y = c
            return i + 1, lax.psum(y, "data")

        _, out = lax.while_loop(cond, body, (jnp.int32(0), x))
        return out

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C8"]
    assert diags[0].severity == analysis.ERROR
    assert "while" in diags[0].path
    assert "axis_index" in diags[0].message
    assert "psum" in diags[0].message


def test_c8_taint_reaches_trip_count_through_carry():
    """fori_loop with an axis_index-derived upper bound: the taint
    rides the loop carry into the cond, not the cond closure — the
    fixpoint over carried values must still mark the trip count."""
    def prog(x):
        n = lax.axis_index("data") + 1
        return lax.fori_loop(0, n,
                             lambda _, y: lax.psum(y, "data"), x)

    diags = analysis.lint(prog, (jnp.ones(4),), axis_env=_ENV)
    assert [d.id for d in diags] == ["C8"]


def test_c8_quiet_fixtures():
    """Static-bound while with a collective: fine. Rank-dependent trip
    count WITHOUT collectives in the body: fine (pure local compute may
    legally diverge). Collective inside scan: trip count is static by
    construction — never C8."""
    def static_while(x):
        def cond(c):
            i, _ = c
            return i < 3

        def body(c):
            i, y = c
            return i + 1, lax.psum(y, "data")

        _, out = lax.while_loop(cond, body, (jnp.int32(0), x))
        return out

    def tainted_no_collective(x):
        n = lax.axis_index("data") + 1
        return lax.fori_loop(0, n, lambda _, y: y * 2.0, x)

    def collective_scan(x):
        def step(c, _):
            return lax.psum(c, "data"), None
        out, _ = lax.scan(step, x, jnp.arange(3))
        return out

    x = jnp.ones(4)
    assert analysis.lint(static_while, (x,), axis_env=_ENV) == []
    assert analysis.lint(tainted_no_collective, (x,), axis_env=_ENV) == []
    assert analysis.lint(collective_scan, (x,), axis_env=_ENV) == []


def test_allowlist_suppresses_by_id_and_path():
    def prog(x):
        return lax.psum(x.astype(jnp.float32), "data")

    x = jnp.ones(8, jnp.bfloat16)
    assert analysis.lint(prog, (x,), axis_env=_ENV, allow=("C3",)) == []
    [d] = analysis.lint(prog, (x,), axis_env=_ENV)
    assert analysis.lint(prog, (x,), axis_env=_ENV,
                         allow=(f"C3:{d.path}",)) == []


# ---- shipped programs: every combination must lint clean -------------

@pytest.mark.parametrize("name", programs.program_names())
def test_shipped_program_is_clean(hvdlint_shipped, name):
    hvdlint_shipped(name)


@pytest.mark.parametrize("name", ["llama_train_step",
                                  "pipeline_interleaved_1f1b"])
def test_shipped_moe_program_is_clean(hvdlint_shipped, name):
    hvdlint_shipped(name, config="tiny_moe")


def test_full_suite_without_shard_map(monkeypatch):
    """The analyzer traces with ``axis_env`` and never asks for
    ``jax.shard_map``, a mesh or devices: take the attribute away and
    run the whole shipped-program sweep."""
    monkeypatch.delattr(jax, "shard_map")
    results = programs.lint_all()
    assert set(results) == set(programs.program_names())
    bad = {n: [d.format() for d in ds]
           for n, ds in results.items() if ds}
    assert not bad, bad


def test_cli_single_program_and_exit_codes(capsys):
    from horovod_tpu.analysis.lint import main

    assert main(["--program", "pipeline_gpipe"]) == 0
    out = capsys.readouterr().out
    assert "pipeline_gpipe: clean" in out
    assert main(["--list"]) == 0
    assert "llama_train_step" in capsys.readouterr().out
