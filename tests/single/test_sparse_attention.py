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


def _spread(t, block, visits_of):
    """bool [1, t, 1, t / block]: every token its own block; a tile whose
    own visit is ``own`` (``per`` blocks a visit) also the visits
    ``visits_of(own)`` [0 .. own), visit ``i`` of them by the tile's token
    ``i % 16`` ALONE, in its first block where ``i`` is even and its last
    where odd: what a softmax step takes side by side was chosen by
    different tokens, and a block beside a chosen one by nobody."""
    nb = t // block
    per = sa.BLOCKS_A_VISIT if nb % sa.BLOCKS_A_VISIT == 0 else 1
    sel = np.zeros((1, t, 1, nb), bool)
    sel[0, np.arange(t), 0, np.arange(t) // block] = True
    for tile in range(t // sa.TOKENS_A_TILE):
        first = tile * sa.TOKENS_A_TILE
        for i, visit in enumerate(visits_of(first // (per * block))):
            sel[0, first + i % sa.TOKENS_A_TILE, 0,
                visit * per + (i % 2) * (per - 1)] = True
    return sel


@pytest.mark.parametrize("t, visits_of", [
    (64, None), (320, lambda own: range(own))],
    ids=["two_tokens_of_a_tile", "a_step_of_pairs_chosen_by_different_tokens"])
def test_a_row_attends_its_own_set_and_nothing_else(kernels, t, visits_of):
    """Every block's values point their own way, so a row's output says
    which blocks reached it: its token's set, each block with a key at
    or before the token, and no other. Two tokens of one tile with
    different sets; and twenty tiles whose listed visits were each chosen
    by another token of the tile, several of them a softmax step."""
    block = 16
    q, k, _, _ = _operands(t, F32, h=2, g=1)
    if visits_of is None:
        sel = np.zeros((1, t, 1, t // block), bool)
        sel[0, :, 0, 0] = True
        sel[0, np.arange(t), 0, np.arange(t) // block] = True
        sel[0, 49, 0, 1] = True                  # token 49 alone: block 1
    else:
        sel = _spread(t, block, visits_of)
    nb = t // block
    v = jnp.repeat(jnp.eye(nb, 32, dtype=F32), block, 0)[None, :, None, :]
    o = jax.jit(lambda q, k, v, table: sa.sparse_attention(
        q, k, v, table, block))(q, k, v, sa._pack(jnp.asarray(sel)))
    reached = np.asarray(o)[0, :, :, :nb] > 0              # [t, h, nb]
    assert (reached == sel[0, :, 0, None, :]).all(), np.argwhere(
        reached != sel[0, :, 0, None, :])[:8]


# What the walk's list can get wrong: a tile (the last ones of the
# sequence have the most visits before their own) lists no visit, one
# fewer than a step of the walk takes, as many, one more, or every visit.
_LISTS = {
    "empty_but_for_its_own_pair": lambda own: range(0),
    "a_visit_short_of_a_step": lambda own: range(
        min(own, sa.VISITS_A_STEP - 1)),
    "a_whole_step": lambda own: range(min(own, sa.VISITS_A_STEP)),
    "a_visit_over_a_step": lambda own: range(
        own - min(own, sa.VISITS_A_STEP + 1), own),
    "every_begun_pair": lambda own: range(own),
}


@pytest.fixture(scope="module")
def pair_and_mask():
    """(the pair's readings, the masked form's) with the table an
    ARGUMENT: the cases of one length share a compiled program."""
    def readings(attend):
        def loss(q, k, v, w, table):
            o = attend(q, k, v, table, 16).astype(F32)
            return jnp.sum(o * w), o

        def run(q, k, v, w, table):
            grads, o = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
                q, k, v, w, table)
            return (o,) + grads
        return jax.jit(run)
    return readings(sa.sparse_attention), readings(sa._masked_attention)


@pytest.mark.parametrize("case", sorted(_LISTS))
@pytest.mark.parametrize("blocks_a_visit", [2, 1])
def test_the_walk_lists_what_a_tile_chose(kernels, pair_and_mask,
                                          blocks_a_visit, case):
    """The pair against the masked form, forward and the three gradients
    in float32 (the order of float32 additions: 2e-5 of the largest
    entry), on tables that fix the LENGTH of a tile's list: none but its
    own pair, a step's worth less one, a step's worth, one more, every
    begun pair; two blocks a visit, and one where their number is odd
    (the padded tail of a list is then made of visits nobody chose)."""
    visits = sa.VISITS_A_STEP + 2
    t = visits * blocks_a_visit * 16 if blocks_a_visit == 2 \
        else (visits + visits % 2 + 1) * 16
    assert (t // 16) % 2 == blocks_a_visit % 2
    q, k, v, w = _operands(t, F32, h=2, g=1, seed=3)
    if case == "every_begun_pair":       # by every token: plain causal
        sel = np.tril(np.ones((t // 16, t // 16), bool)).repeat(16, 0)[
            None, :, None, :]
    else:
        sel = _spread(t, 16, _LISTS[case])
    table = sa._pack(jnp.asarray(sel))
    pair, mask = pair_and_mask
    with jax.default_matmul_precision("highest"):
        got, want = pair(q, k, v, w, table), mask(q, k, v, w, table)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < 2e-5, (name, err)


def test_a_tile_lies_in_one_visit(kernels):
    """The own visit is the one with a causal edge: blocks narrower than
    a tile's tokens are refused by name."""
    q, k, v, _ = _operands(64, F32, h=2, g=1)
    with pytest.raises(ValueError, match="a tile has to lie in one visit"):
        sa.sparse_attention(q, k, v, jnp.zeros((1, 1, 4, 16), jnp.int32), 4)


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


def _top_k_sets(key, count):
    """The oracle: ``lax.top_k``'s values and indices turned into sets
    (ties to the lower index, -inf never), as the selection formed them
    before it counted."""
    value, index = jax.lax.top_k(key, min(count, key.shape[-1]))
    picked = (index[..., None] == jnp.arange(key.shape[-1])) \
        & (value[..., None] > -jnp.inf)
    return jnp.any(picked, -2)


def _keys(case, rows=24, n=32, count=8):
    """-> (keys [1, rows, 2, n] float32 as ``_rows_chosen`` forms them:
    +inf the forced, -inf the not begun, scores floored at -1.0; how many
    to choose)."""
    rng = np.random.default_rng(sum(map(ord, case)))
    key = (rng.random((1, rows, 2, n)) * 16).astype(np.float32)
    r = np.arange(rows)[:, None, None]
    at = np.arange(n)
    if case.startswith("forced_run_"):
        # a run of +inf (the window) behind a lone one (the first block)
        length = {"shorter": count - 3, "equal": count - 1,
                  "longer": count + 4}[case[len("forced_run_"):]]
        start = 2 + r % (n - length - 2)
        key[0] = np.where((at >= start) & (at < start + length), np.inf,
                          key[0])
        key[..., 0] = np.inf
    elif case == "equal_scores_across_the_place":
        key = np.round(key / 4)                  # five values, many of each
    elif case == "the_floor_across_the_place":
        # a few scores over the floor, then the not begun
        key[0] = np.where(at < r % count, key[0], -1.0)
        key[0, :, :, :n - 6] = key[0, :, :, rng.permutation(n - 6)].transpose(
            1, 2, 0)
        key[..., n - 6:] = -np.inf
    elif case == "fewer_begun_than_chosen":
        key[0] = np.where(at <= r % (count - 1), key[0], -np.inf)
        key[0, :, :, 0] = np.inf
    elif case == "as_many_chosen_as_blocks":
        count = n
        key[0] = np.where(at <= r, key[0], -np.inf)
    elif case == "more_chosen_than_blocks":
        count = 2 * n
        key[0] = np.where(at <= r, key[0], -np.inf)
    elif case == "a_negative_zero_beside_a_zero":
        zero = np.where(rng.random(key.shape) < 0.5, 0.0, -0.0)
        key = np.where(rng.random(key.shape) < 0.2, key, zero).astype(
            np.float32)
        key[..., n - 6:] = -np.inf
    else:
        assert case == "random_scores", case
    return jnp.asarray(key), count


@pytest.mark.parametrize("case", [
    "random_scores", "forced_run_shorter", "forced_run_equal",
    "forced_run_longer", "equal_scores_across_the_place",
    "the_floor_across_the_place", "fewer_begun_than_chosen",
    "as_many_chosen_as_blocks", "more_chosen_than_blocks",
    "a_negative_zero_beside_a_zero"])
def test_the_counted_threshold_chooses_what_top_k_chose(case):
    """``_largest`` finds a row's set from its ``count``-th largest key,
    by counting; the set is ``lax.top_k``'s bit for bit: on forced runs
    shorter than, as long as and longer than the number to choose, on
    equal scores and on the floor of -1.0 across the last place (the
    lower index wins), where fewer blocks have begun than are chosen,
    where every block is, and on the two zeros (the order is the bits':
    -0.0 below +0.0)."""
    key, count = _keys(case)
    got = np.asarray(jax.jit(lambda k: sa._largest(k, count))(key))
    want = np.asarray(jax.jit(lambda k: _top_k_sets(k, count))(key))
    assert got.dtype == bool and got.shape == key.shape
    assert (got == want).all(), np.argwhere((got != want).any(-1))
    # the cases are what they say: ties stand across the last place, and
    # a row never holds more than it may
    assert (got.sum(-1) <= count).all()
    assert not got[np.asarray(key) == -np.inf].any()
    if "across_the_place" in case or "zero" in case:
        k = np.asarray(key)
        last = np.sort(k, -1)[..., -count, None]
        assert ((k == last).sum(-1) > 1).any()
        assert (((k == last) & ~got).any(-1) & ((k == last) & got).any(-1)
                ).any()


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
