"""Latent attention's seam between the up-projections and the flash
kernels as a kernel pair (``horovod_tpu/ops/mla_prep.py``), in pallas
interpret mode on the CPU, against the expressions of
``models/llama.py:_latent_attention`` that run off the TPU (``_rope`` on
the rotated slices, the concatenations, one key for all heads, the
transposes into ``[B, H, T, d]``): outputs and the gradients of ``yq``,
``ykv`` and ``k_r``; and the predicate that says where the pair runs.

Shapes: 128 + 64 beside 128 (the widths a head of ``yq`` must have to
start off a lane tile's edge), 32 tokens in steps of 16 (two grid steps a
sequence), and heads that fill a trip of the walk (8: four groups of
two), that leave the last trip short (6: three groups a trip) and that
take a trip a group (10: five groups)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama
from horovod_tpu.ops import mla_prep as module

F32, BF16 = jnp.float32, jnp.bfloat16
B, T = 2, 32
WIDTHS = DN, DR, DV = 128, 64, 128
MULT = 1.2079
# positions that differ a sequence: the table is [B, T, dr]
POSITIONS = jnp.arange(T)[None] + 7 * jnp.arange(B)[:, None]
NAMES = ["q", "k", "v", "d yq", "d ykv", "d k_r"]


@pytest.fixture
def kernels(monkeypatch):
    """Run the seam on its kernels, 16 tokens a grid step."""
    monkeypatch.setattr(module, "_INTERPRET", True)
    monkeypatch.setattr(module, "TOKENS_A_STEP", 16)


def _operands(dtype, H, seed=0, widths=WIDTHS):
    dn, dr, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    flat = [jax.random.normal(k, (B, T, n), F32).astype(dtype)
            for k, n in zip(ks, (H * (dn + dr), H * (dn + dv), dr))]
    weights = [jax.random.normal(k, (B, H, T, n), F32)
               for k, n in zip(ks[3:], (dn + dr, dn + dr, dv))]
    return flat, weights


def _freqs(dr):
    return (1e4 ** (-np.arange(0, dr, 2) / dr)).astype(np.float32)


def _expressions(yq, ykv, k_r):
    """What ``_latent_attention`` runs off the TPU, then
    ``flash_attention``'s transposes."""
    dn, dr = DN, k_r.shape[2]
    H = yq.shape[2] // (dn + dr)
    turn = lambda x: llama._rope(  # noqa: E731
        x, POSITIONS, None, freqs=jnp.asarray(_freqs(dr)), mult=MULT)
    q = yq.reshape(B, T, H, dn + dr)
    kv = ykv.reshape(B, T, H, -1)
    q_r, k_r = turn(q[..., dn:]), turn(k_r[:, :, None, :])
    q = jnp.concatenate([q[..., :dn], q_r], -1)
    k = jnp.concatenate([kv[..., :dn],
                         llama._one_key_for_all_heads(k_r, H)], -1)
    return [x.transpose(0, 2, 1, 3) for x in (q, k, kv[..., dn:])]


def _pair(yq, ykv, k_r, mesh=None):
    return module.mla_prep(yq, ykv, k_r, POSITIONS, _freqs(k_r.shape[2]),
                           MULT, DN, mesh)


def _read(fn, flat, weights):
    """(q, k, v and the gradients of a weighted sum of them in ``yq``,
    ``ykv`` and ``k_r``); the weights are an argument, so one compiled
    program reads any cotangent."""
    def loss(flat):
        outs = fn(*flat)
        return sum(jnp.sum(o.astype(F32) * w)
                   for o, w in zip(outs, weights)), outs

    (_, outs), grads = jax.value_and_grad(loss, has_aux=True)(flat)
    return dict(zip(NAMES, [*outs, *grads]))


def _l2(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return np.linalg.norm(got - ref) / (np.linalg.norm(ref) + 1e-30)


@pytest.mark.parametrize("dtype, H, widths", [
    ("float32", 8, WIDTHS), ("float32", 6, WIDTHS), ("float32", 10, WIDTHS),
    # a head of whole slabs (a group is one head), values of two
    ("float32", 3, (128, 128, 256)),
    ("bfloat16", 8, WIDTHS), ("bfloat16", 10, WIDTHS)], ids=str)
def test_the_pair_is_the_expressions(kernels, dtype, H, widths):
    """Values and every gradient. In float32 to its last digits. In
    bfloat16 the kernels round ONCE behind the rotation where ``_rope``
    rounds its table and three times more, and sum ``dk_r`` over the
    heads in float32, so each is held to what float32 says of the same
    rounded inputs: the kernels stand no further from it than the
    expressions do."""
    dt = jnp.dtype(dtype)
    flat, weights = _operands(dt, H, widths=widths)
    expressions = jax.jit(lambda f, w: _read(_expressions, f, w))
    ref = expressions(flat, weights)
    got = jax.jit(lambda f, w: _read(_pair, f, w))(flat, weights)
    for name in NAMES:
        assert got[name].shape == ref[name].shape, name
        assert got[name].dtype == dt, name
    if dt == F32:
        for name in NAMES:
            assert _l2(got[name], ref[name]) < 2e-6, name
        return
    exact = expressions([a.astype(F32) for a in flat], weights)
    for name in NAMES:
        mine, theirs = (_l2(a[name], exact[name]) for a in (got, ref))
        assert mine < 1.1 * theirs + 1e-3, (name, mine, theirs)


@pytest.fixture(scope="module")
def bf16_outputs():
    """One compiled pair on bf16 operands of four heads, its weights an
    argument: the cases below read what they ask of it."""
    flat, weights = _operands(BF16, 4, seed=1)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(module, "_INTERPRET", True)
        patch.setattr(module, "TOKENS_A_STEP", 16)
        program = jax.jit(lambda f, w: _read(_pair, f, w))
        program(flat, weights)            # traced under the switch
    return flat, weights, program


def test_what_does_not_turn_is_a_relayout_and_nothing_else(bf16_outputs):
    flat, weights, program = bf16_outputs
    got = program(flat, weights)
    yq, ykv = (np.asarray(y.astype(F32)).reshape(B, T, 4, -1)
               .transpose(0, 2, 1, 3) for y in flat[:2])
    for name, mine, theirs in (("q_n", got["q"][..., :DN], yq[..., :DN]),
                               ("k_n", got["k"][..., :DN], ykv[..., :DN]),
                               ("v", got["v"], ykv[..., DN:])):
        np.testing.assert_array_equal(np.asarray(mine, np.float32), theirs,
                                      err_msg=name)


def test_every_head_holds_the_same_rotated_key(bf16_outputs):
    flat, weights, program = bf16_outputs
    k_r = np.asarray(program(flat, weights)["k"][..., DN:], np.float32)
    assert np.abs(k_r).max() > 0
    np.testing.assert_array_equal(k_r, np.broadcast_to(k_r[:, :1],
                                                       k_r.shape))


def test_dk_r_is_the_sum_over_the_heads(bf16_outputs):
    """The gradient of the shared ``k_r`` under a cotangent on every
    head's key is the sum (in float32, rounded once) of what a cotangent
    on each head alone leaves."""
    flat, weights, program = bf16_outputs
    whole = program(flat, weights)["d k_r"]
    heads = [program(flat, [weights[0], weights[1].at[:, :j].set(0)
                            .at[:, j + 1:].set(0), weights[2]])["d k_r"]
             for j in range(4)]
    assert all(np.abs(np.asarray(h, np.float32)).max() > 0 for h in heads)
    assert _l2(whole, sum(h.astype(F32) for h in heads)) < 4e-3


def test_the_pair_shards_itself_over_a_mesh(kernels):
    """Batch over ``data``, heads in whole groups over ``tensor``: each
    device runs the kernels on its shard, and ``dk_r`` is summed over
    the heads of both shards."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "tensor"))
    flat, weights = _operands(F32, 4)
    alone = jax.jit(lambda f, w: _read(_pair, f, w))(flat, weights)
    split = jax.jit(lambda f, w: _read(
        lambda *a: _pair(*a, mesh=mesh), f, w))(flat, weights)
    for name in NAMES:
        assert _l2(split[name], alone[name]) < 2e-6, name
    assert split["q"].sharding.spec[:2] == ("data", "tensor")


_X = jnp.zeros((1, 32, 8))


@pytest.mark.parametrize("what, args, runs", [
    # the input, the heads, the widths: q_n / k_n, the rotated slice, v
    ("128 + 64 beside 128, heads in pairs", (_X, 32, 128, 64, 128), True),
    ("a rotated slice as wide as a slab", (_X, 3, 128, 128, 128), True),
    ("values of two slabs", (_X, 4, 128, 64, 256), True),
    ("an odd head", (_X, 5, 128, 64, 128), False),
    ("q_n that is no slab", (_X, 4, 64, 64, 128), False),
    ("values that are no slab", (_X, 4, 128, 64, 64), False),
    ("a rotated slice that divides no slab", (_X, 4, 128, 48, 128), False),
    ("tokens that fill no packed tile", (jnp.zeros((1, 24, 8)), 4, 128, 64,
                                         128), False),
])
def test_where_the_pair_runs_is_read_off_the_input(kernels, what, args,
                                                   runs):
    assert module.on_kernels(*args) is runs, what


def test_cpu_operands_take_the_expressions():
    assert not module.on_kernels(_X, 32, 128, 64, 128)


def test_interpret_mode_on_a_tpu_is_refused(kernels, monkeypatch):
    from horovod_tpu.ops import _platform

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        module.on_kernels(_X, 32, 128, 64, 128)
