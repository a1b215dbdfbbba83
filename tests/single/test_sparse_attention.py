"""``ops/sparse_attention.py``: the selection's five steps on small cases
worked by hand, and the kernel pair in interpret mode against the
explicit-mask form in ``jax.numpy`` (which ``tests/single/
test_sala_reference.py`` holds to the float32 reference), at the smallest
grid that has several tiles, several blocks and blocks left out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import sparse_attention as sa

pytestmark = pytest.mark.quick
F32 = jnp.float32
SIZES = dict(block=16, topk=3, kernel=8, stride=4, init_blocks=1,
             window_blocks=1)


def _operands(t, dtype, h=4, g=2, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, w = (jax.random.normal(k, (1, t, h, d), F32).astype(dtype)
            for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(k, (1, t, g, d), F32).astype(dtype)
            for k in ks[1:3])
    return q, k, v, w


@pytest.fixture
def kernels(monkeypatch):
    """The kernel pair in interpret mode, and nothing else: the masked
    form is taken away."""
    def no_mask(*_):
        raise AssertionError("the explicit-mask form ran")

    masked = sa._masked_attention
    monkeypatch.setattr(sa, "_INTERPRET", True)
    monkeypatch.setattr(sa, "_masked_attention", no_mask)
    return masked


def _readings(attend, table, block):
    """-> jitted (o, dq, dk, dv of ``sum(o * w)``), float32."""
    def loss(q, k, v, w):
        o = attend(q, k, v, table, block).astype(F32)
        return jnp.sum(o * w.astype(F32)), o

    def run(q, k, v, w):
        grads, o = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v, w)
        return tuple(x.astype(F32) for x in (o,) + grads)
    return jax.jit(run)


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                        ("bfloat16", 2e-2)])
def test_the_kernel_pair_is_the_masked_softmax(kernels, dtype, tol):
    """Four tiles of 16 tokens x 2 heads a group, six blocks of which a
    late token sees three: forward and the three gradients. float32:
    the order of float32 additions (2e-5 of the largest entry); bf16:
    ``p`` and ``ds`` rounded where they enter a matmul, as the masked
    form rounds ``p`` (2e-2)."""
    q, k, v, w = _operands(96, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        table = sa.select_blocks(q, k, **SIZES)
        assert int((sa.chosen(table).sum(-1) == 3).sum()) > 0
        got = _readings(sa.sparse_attention, table, 16)(q, k, v, w)
        want = _readings(kernels, table, 16)(q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < tol, (name, err)


def test_a_row_attends_its_own_set_and_nothing_else(kernels):
    """Two tokens of one tile with different sets: a value planted in a
    block only the second chose reaches the second's rows alone."""
    t, block = 64, 16
    q, k, v, _ = _operands(t, F32, h=2, g=1)
    sel = np.zeros((1, t, 1, t // block), bool)
    sel[0, :, 0, 0] = True
    sel[0, np.arange(t), 0, np.arange(t) // block] = True
    sel[0, 49, 0, 1] = True                      # token 49 alone: block 1
    v = jnp.zeros_like(v).at[0, 16:32].set(1.0)   # block 1's values
    o = sa.sparse_attention(q, k, v, sa._pack(jnp.asarray(sel)), block)
    reached = np.asarray(jnp.abs(o).sum((0, 2, 3)) > 0)
    assert reached[49] and reached[16:32].all()
    assert not reached[32:49].any() and not reached[50:].any()


def test_the_selection_by_hand():
    """Keys that point one way in block 1 and another everywhere else,
    queries that ask for block 1: past the forced blocks (the first, the
    token's own) the one free choice is block 1, from the first token
    that may see one of its windows whole."""
    t, d = 96, 8
    k = jnp.tile(jnp.eye(d, dtype=F32)[1], (1, t, 1, 1))
    k = k.at[0, 16:32, 0].set(jnp.eye(d, dtype=F32)[0])
    q = 50.0 * jnp.tile(jnp.eye(d, dtype=F32)[0], (1, t, 2, 1))
    got = np.asarray(sa.chosen(sa.select_blocks(q, k, **SIZES)))[0, :, 0]
    at = np.arange(t)
    assert got[:, 0].all() and got[at, at // 16].all()
    assert (got.sum(-1) == np.minimum(at // 16 + 1, 3)).all()
    assert got[48:, 1].all()
    # with nothing to tell the blocks apart: the lower index
    flat = np.asarray(sa.chosen(sa.select_blocks(
        jnp.zeros_like(q), k, **SIZES)))[0, :, 0]
    assert flat[48:, 1].all() and not flat[64:, 2].any()
    # a window counts from the token at which it lies wholly behind:
    # window j of block 1's first key ends at 16 + 7
    scores = sa._block_scores(
        jnp.ones((1, 23)), (4 * np.arange(23) + 7) <= 23, 4, 6)
    assert np.isfinite(np.asarray(scores)[0]).tolist() == [
        True, True, False, False, False, False]


def test_the_table_packs_and_unpacks():
    sel = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 64, 2, 4))
    table = sa._pack(sel)
    assert table.shape == (2, 2, 64 // sa.TOKENS_A_TILE, 4)
    assert table.dtype == jnp.int32
    assert (np.asarray(sa.chosen(table)) == np.asarray(sel)).all()


def test_no_gradient_passes_the_selection():
    q, k, _, _ = _operands(64, F32)
    grads = jax.grad(lambda q, k: sa.chosen(sa.select_blocks(
        q, k, **SIZES)).astype(F32).sum() + 0.0 * q.sum(),
        argnums=(0, 1))(q, k)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


@pytest.mark.parametrize("t, sizes, match", [
    (40, SIZES, "has to divide"),
    (64, {**SIZES, "stride": 3}, "has to divide"),
    (64, {**SIZES, "kernel": 6}, "no multiple of the stride")])
def test_what_does_not_divide_is_refused_by_name(t, sizes, match):
    q, k, _, _ = _operands(t, F32)
    with pytest.raises(ValueError, match=match):
        sa.select_blocks(q, k, **sizes)
