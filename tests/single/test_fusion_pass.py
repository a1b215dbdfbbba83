"""The jaxpr machinery under the jit-lane compute/collective fusion
(``horovod_tpu.parallel.fusion``), pinned in isolation:

- ``interleave_collectives`` — the reorder pass must move each
  reduce-scatter off the program tail to the point its operand is
  ready, WITHOUT changing the math (bit-identical replay under the
  vmap(axis_name) emulation) and without touching collective-free
  programs;
- ``segment_closed_jaxpr`` — segmented replay runs the monolithic
  program's equations in their order (bit-equal to it when both run a
  primitive at a time; within ``_ULPS`` when the segments are compiled,
  each an XLA program of its own whose fusions round otherwise) and
  fires ``on_boundary`` once per segment (the hook the host lane hangs
  its eager reduce-scatters on);
- ``grad_bucket_cuts`` — bucket readiness points are consistent with
  the producing equations, so wire issue order follows gradient
  availability.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel import fusion
from horovod_tpu.parallel.fusion import (
    _jcore,
    grad_bucket_cuts,
    interleave_collectives,
    segment_closed_jaxpr,
)
from horovod_tpu.parallel.zero import zero_bucket_layout

pytestmark = pytest.mark.quick


def _bits(a):
    return np.asarray(a, dtype=np.float32).view(np.uint32)


#: a compiled segment against the same equations run one at a time:
#: XLA CPU fuses ``bb + bb * f`` of the w1 gradient into one
#: multiply-add loop (0.45 of this unit measured, PR 46)
_ULPS = 4


def _assert_bit_equal(got, want):
    for a, b in zip(got, want, strict=True):
        assert np.array_equal(_bits(a), _bits(b))


def _assert_within_ulps(got, want):
    """Within ``_ULPS`` units in the last place of each tensor's
    largest element."""
    for a, b in zip(got, want, strict=True):
        a, b = np.asarray(a), np.asarray(b)
        unit = np.finfo(np.float32).eps * np.abs(b).max()
        assert np.abs(a - b).max() <= _ULPS * unit


def _bunched(x, w):
    # Backward-shaped: all the compute first, every scatter at the
    # tail.  16x16 operands sit above the pass's 64-element hoist
    # threshold, so the dots count as immovable compute.
    a = x @ w
    b = jnp.tanh(a) @ w
    s1 = lax.psum_scatter(a.reshape(-1), "data", scatter_dimension=0,
                          tiled=True)
    s2 = lax.psum_scatter(b.reshape(-1), "data", scatter_dimension=0,
                          tiled=True)
    return s1, s2


def _trace_bunched():
    x, w = jnp.ones((16, 16)), jnp.ones((16, 16))
    return jax.make_jaxpr(_bunched, axis_env=[("data", 2)])(x, w)


def test_interleave_moves_scatters_off_the_tail():
    closed = _trace_bunched()
    orig = [e.primitive.name for e in closed.jaxpr.eqns]
    # Sanity on the fixture itself: tail-bunched.
    assert orig.index("reduce_scatter") > max(
        i for i, p in enumerate(orig) if p == "dot_general")

    re = interleave_collectives(closed)
    new = [e.primitive.name for e in re.jaxpr.eqns]
    # Same equations, different schedule.
    assert sorted(new) == sorted(orig)
    # The first scatter now issues before the remaining compute...
    assert new.index("reduce_scatter") < new.index("tanh")
    # ...and each scatter still follows at least one dot (its operand).
    dots = [i for i, p in enumerate(new) if p == "dot_general"]
    scatters = [i for i, p in enumerate(new) if p == "reduce_scatter"]
    assert scatters[0] > dots[0]
    assert scatters[1] > dots[1]


def test_interleave_preserves_semantics_under_vmap():
    closed = _trace_bunched()
    re = interleave_collectives(closed)
    f_orig = _jcore.jaxpr_as_fun(closed)
    f_re = _jcore.jaxpr_as_fun(re)

    key = jax.random.PRNGKey(3)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (2, 16, 16))
    w = jax.random.normal(kw, (2, 16, 16))
    outs_o = jax.vmap(f_orig, axis_name="data")(x, w)
    outs_r = jax.vmap(f_re, axis_name="data")(x, w)
    for o, r in zip(outs_o, outs_r):
        assert np.array_equal(_bits(o), _bits(r))


def test_interleave_is_identity_without_collectives():
    def prog(x, w):
        return jnp.tanh(x @ w) @ w

    closed = jax.make_jaxpr(prog)(jnp.ones((16, 16)), jnp.ones((16, 16)))
    re = interleave_collectives(closed)
    assert ([e.primitive.name for e in re.jaxpr.eqns]
            == [e.primitive.name for e in closed.jaxpr.eqns])


def _grad_program():
    def loss_fn(params, x):
        h = jnp.tanh(x @ params["w1"])
        h = jnp.tanh(h @ params["w2"] + params["b"])
        return jnp.sum(h ** 2)

    params = {
        "w1": jax.random.normal(jax.random.PRNGKey(0), (16, 32)) * 0.1,
        "w2": jax.random.normal(jax.random.PRNGKey(1), (32, 8)) * 0.1,
        "b": jnp.zeros((8,)),
    }
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16))
    leaves, treedef = jax.tree.flatten(params)

    def flat_grad(*flat):
        p = jax.tree.unflatten(treedef, flat)
        loss, g = jax.value_and_grad(loss_fn)(p, x)
        return (loss, *jax.tree.leaves(g))

    return flat_grad, leaves


def test_segment_replay_bit_equal_and_boundary_count():
    flat_grad, leaves = _grad_program()
    closed = jax.make_jaxpr(flat_grad)(*leaves)
    n = len(closed.jaxpr.eqns)
    assert n >= 6  # enough equations for a meaningful split
    cuts = [n // 3, (2 * n) // 3]

    prog = segment_closed_jaxpr(closed, cuts)
    assert len(prog.segments) == len(cuts) + 1

    fired = []
    outs, env = prog.run(*leaves, on_boundary=lambda k, e: fired.append(k))
    assert fired == list(range(len(prog.segments)))

    direct = flat_grad(*leaves)  # eager: a primitive an XLA program
    # The segmentation reorders no arithmetic: run as the eager call
    # runs, the replay is the same bits.
    with jax.disable_jit():
        exact, _ = prog.run(*leaves)
    _assert_bit_equal(exact, direct)
    _assert_within_ulps(outs, direct)
    # read_output resolves the same values out of the final env.
    _assert_bit_equal(
        [prog.read_output(env, pos) for pos in range(len(direct))], outs)


def test_grad_bucket_cuts_follow_producers():
    flat_grad, leaves = _grad_program()
    closed = jax.make_jaxpr(flat_grad)(*leaves)
    n = len(closed.jaxpr.eqns)
    layout = zero_bucket_layout(leaves, n_shards=2, bucket_bytes=1024)
    assert len(layout.buckets) >= 2  # tiny buckets: multiple wire chunks

    cuts, ready = grad_bucket_cuts(closed, layout)
    assert len(ready) == len(layout.buckets)
    assert cuts == sorted(set(cuts))
    assert all(0 < c < n for c in cuts)
    # Every bucket's readiness point is a real cut (or program end),
    # and segmenting at the cuts still replays the exact gradients.
    for r in ready:
        assert r in cuts or r in (0, n)
    prog = segment_closed_jaxpr(closed, cuts)
    direct = flat_grad(*leaves)
    with jax.disable_jit():
        exact, _ = prog.run(*leaves)
    _assert_bit_equal(exact, direct)
    outs, _ = prog.run(*leaves)
    _assert_within_ulps(outs, direct)
    # Issue order is by readiness — the contract the host lane uses.
    order = sorted(range(len(ready)), key=ready.__getitem__)
    assert [ready[i] for i in order] == sorted(ready)


def test_fusion_knob_env_and_override():
    # set_jit_fusion overrides the env; None restores env control.
    import os

    old = os.environ.get("HOROVOD_JIT_FUSION")
    try:
        os.environ["HOROVOD_JIT_FUSION"] = "0"
        fusion.set_jit_fusion(None)
        assert fusion.jit_fusion_enabled() is False
        fusion.set_jit_fusion(True)
        assert fusion.jit_fusion_enabled() is True
        os.environ["HOROVOD_JIT_FUSION"] = "1"
        fusion.set_jit_fusion(None)
        assert fusion.jit_fusion_enabled() is True
        fusion.set_jit_fusion(False)
        assert fusion.jit_fusion_enabled() is False
    finally:
        fusion.set_jit_fusion(None)
        if old is None:
            os.environ.pop("HOROVOD_JIT_FUSION", None)
        else:
            os.environ["HOROVOD_JIT_FUSION"] = old
