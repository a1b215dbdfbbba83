"""The seam between the q/k/v projections and the flash kernels as a
kernel pair (``horovod_tpu/ops/qk_prep.py``), in pallas interpret mode
on the CPU, against the expressions of ``models/llama.py`` that run off
the TPU (``_rms`` a head, ``_rope``, the transpose into ``[B, H, T,
d]``): outputs and every gradient, both gains' among them; and the
predicate that says where the pair runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama
from horovod_tpu.ops import qk_prep as module

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5


@pytest.fixture
def kernels(monkeypatch):
    """Run the seam on its kernels, a grid step taking so many
    tokens."""
    def switch(tokens=16):
        monkeypatch.setattr(module, "_INTERPRET", True)
        monkeypatch.setattr(module, "TOKENS_A_STEP", tokens)
    return switch


def _operands(dtype, B, T, H, Hkv, d, norm, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    flat = [jax.random.normal(k, (B, T, h * d), F32).astype(dtype)
            for k, h in zip(ks, (H, Hkv, Hkv))]
    gains = [(1 + 0.2 * jax.random.normal(k, (d,), F32)).astype(dtype)
             if norm else None for k in ks[3:5]]
    weights = [jax.random.normal(k, (B, h, T, d), F32)
               for k, h in zip(ks[5:], (H, Hkv, Hkv))]
    # positions that differ a sequence: the table is [B, T, d]
    positions = jnp.arange(T)[None] + 7 * jnp.arange(B)[:, None]
    return flat, gains, weights, positions


def _expressions(flat, gains, positions, theta, d):
    """What ``mix`` runs off the TPU, then ``flash_attention``'s
    transposes."""
    outs = []
    for y, gain, turns in zip(flat, (*gains, None), (True, True, False)):
        y = y.reshape(*y.shape[:2], -1, d)
        if gain is not None:
            y = llama._rms(y, gain, EPS)
        if turns and theta is not None:
            y = llama._rope(y, positions, theta)
        outs.append(y.transpose(0, 2, 1, 3))
    return outs


def _readings(fn, flat, gains, weights):
    """(q, k, v, and the gradients of a weighted sum of them in the
    three projections and the gains that are there)."""
    norm = gains[0] is not None

    def loss(flat, gains):
        outs = fn(flat, gains)
        return sum(jnp.sum(o.astype(F32) * w)
                   for o, w in zip(outs, weights)), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1) if norm else 0, has_aux=True))(flat, gains)
    dflat, dgains = grads if norm else (grads, [])
    names = ["q", "k", "v", "d yq", "d yk", "d yv", "d q_gain", "d k_gain"]
    return dict(zip(names, [*outs, *dflat, *dgains]))


def _l2(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return np.linalg.norm(got - ref) / (np.linalg.norm(ref) + 1e-30)


_CASES = [
    # dtype, T, tokens a step, H, Hkv, d, norm, theta
    ("float32", 32, 16, 4, 2, 128, True, 1e4),
    ("float32", 32, 16, 4, 2, 128, True, None),
    ("float32", 32, 16, 4, 1, 128, False, 1e4),
    ("float32", 16, 16, 2, 2, 128, False, None),
    ("float32", 32, 32, 2, 1, 256, True, 1e6),
    ("float32", 48, 32, 3, 3, 256, False, 1e4),   # 48 tokens a step
    ("bfloat16", 32, 16, 4, 2, 128, True, 1e4),
    ("bfloat16", 32, 16, 4, 2, 128, True, None),
    ("bfloat16", 64, 32, 4, 1, 128, False, 1e4),
    ("bfloat16", 32, 32, 2, 1, 256, True, 1e4),
]


@pytest.mark.parametrize(
    "dtype, T, tokens, H, Hkv, d, norm, theta", _CASES,
    ids=lambda v: str(v))
def test_the_pair_is_the_expressions(kernels, dtype, T, tokens, H, Hkv, d,
                                     norm, theta):
    """Values and every gradient. In float32 to its last digits. In
    bfloat16 the kernels round ONCE behind the rotation where ``_rope``
    rounds three times and sum the gains' gradients in float32, so each
    is held to what float32 says of the same rounded inputs: the
    kernels stand no further from it than the expressions do."""
    dt = jnp.dtype(dtype)
    flat, gains, weights, positions = _operands(dt, 2, T, H, Hkv, d, norm)

    def expressions(flat, gains):
        return _expressions(flat, gains, positions, theta, d)

    def pair(flat, gains):
        return module.qk_prep(*flat, *gains, positions, theta, d, EPS)

    ref = _readings(expressions, flat, gains, weights)
    kernels(tokens)
    got = _readings(pair, flat, gains, weights)
    assert got.keys() == ref.keys()
    if dt == F32:
        for name in ref:
            assert got[name].shape == ref[name].shape, name
            assert _l2(got[name], ref[name]) < 2e-6, name
        return
    up = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)  # noqa: E731
    exact = _readings(expressions, up(flat), up(gains), weights)
    for name in ref:
        assert got[name].dtype == BF16 and got[name].shape == ref[name].shape
        mine, theirs = (_l2(a[name], exact[name]) for a in (got, ref))
        assert mine < 1.1 * theirs + 1e-3, (name, mine, theirs)


def test_v_is_a_relayout_and_nothing_else(kernels):
    flat, gains, _, positions = _operands(BF16, 2, 32, 4, 2, 128, True)
    kernels()
    v = module.qk_prep(*flat, *gains, positions, 1e4, 128, EPS)[2]
    np.testing.assert_array_equal(
        np.asarray(v, np.float32),
        np.asarray(flat[2].reshape(2, 32, 2, 128).transpose(0, 2, 1, 3),
                   np.float32))


def test_the_pair_shards_itself_over_a_mesh(kernels):
    """Batch over ``data``, heads over ``tensor``: each device runs the
    kernels on its shard, and the gains' gradients are summed over
    both."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "tensor"))
    flat, gains, weights, positions = _operands(F32, 2, 16, 4, 2, 128, True)
    kernels()
    alone = _readings(lambda f, g: module.qk_prep(
        *f, *g, positions, 1e4, 128, EPS), flat, gains, weights)
    split = _readings(lambda f, g: module.qk_prep(
        *f, *g, positions, 1e4, 128, EPS, mesh), flat, gains, weights)
    for name in alone:
        assert _l2(split[name], alone[name]) < 2e-6, name


_X = jnp.zeros((1, 32, 8))


@pytest.mark.parametrize("what, args, runs", [
    # the input, a head's width, normed a head, dimensions that turn,
    # a sequence axis
    ("a whole rotation of heads of 128", (_X, 128, False, 128, False), True),
    ("a norm a head and no rotation", (_X, 128, True, 0, False), True),
    ("heads of 256, both", (_X, 256, True, 256, False), True),
    ("heads of 64", (_X, 64, True, 64, False), False),
    ("a partial rotation", (_X, 256, True, 64, False), False),
    ("neither a norm nor a rotation", (_X, 128, False, 0, False), False),
    ("a sequence axis", (_X, 128, True, 128, True), False),
    ("tokens that fill no packed tile", (jnp.zeros((1, 24, 8)), 128, True,
                                         128, False), False),
])
def test_where_the_pair_runs_is_read_off_the_input(kernels, what, args,
                                                   runs):
    kernels()
    assert module.on_kernels(*args) is runs, what


def test_cpu_operands_take_the_expressions():
    assert not module.on_kernels(_X, 128, True, 128, False)


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    kernels()
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        module.on_kernels(_X, 128, True, 128, False)
