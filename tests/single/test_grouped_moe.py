"""Dropless grouped-GEMM MoE dispatch (ops/grouped_moe.py) vs the
GShard one-hot path (models/llama.py:_moe_ffn).

When no token exceeds GShard capacity the two are the same function
(same router, gate normalization, aux loss) computed two ways — values
AND gradients must agree. When tokens overflow, GShard drops them on
the residual and grouped (dropless) computes them — a semantic
difference these tests pin on purpose.

The CPU substrate drives grouped_moe's exact one-hot fallback for the
grouped matmul; the megablox kernel itself is bench/TPU-only (its
interpret mode cannot differentiate).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models.llama import _moe_ffn, moe_balance_loss
from horovod_tpu.ops import grouped_moe
from horovod_tpu.ops.grouped_moe import grouped_moe_ffn


def _layer0(cfg, key=0):
    params = llama_init(cfg, jax.random.PRNGKey(key))
    return jax.tree.map(lambda x: x[0], params["layers"])


def _h(cfg, B=2, T=16, key=3):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (B, T, cfg.d_model), jnp.float32)


def _dropless_cfg(**kw):
    # capacity_factor = E makes per-group capacity C = T*K — no routing
    # pattern can overflow it, so GShard provably drops nothing and the
    # two dispatches compute the same math.
    kw.setdefault("capacity_factor", float(kw.get("n_experts", 4)))
    return LlamaConfig.tiny_moe(dtype="float32", remat=False, **kw)


# The module's old bench routing (top-2 of 4) and OLMoE's (top-8 of 64,
# gate weights as the softmax gave them) at a small width.
ROUTINGS = [
    pytest.param({}, id="k2-e4"),
    pytest.param(dict(n_experts=64, n_experts_per_token=8,
                      norm_topk_prob=False), id="k8-e64"),
]


@pytest.mark.parametrize("routing", ROUTINGS)
def test_grouped_moe_matches_gshard_when_dropless(routing):
    cfg = _dropless_cfg(**routing)
    lp = _layer0(cfg)
    h = _h(cfg)
    y_ref, aux_ref = jax.jit(lambda h, lp: _moe_ffn(h, lp, cfg, None))(
        h, lp)
    y, aux = jax.jit(lambda h, lp: grouped_moe_ffn(h, lp, cfg))(h, lp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(aux_ref),
                               rtol=1e-6)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_grouped_moe_gradients_match_gshard(routing):
    # h, the router and the three expert matrices (the other leaves of
    # the layer get an exact zero from both).
    cfg = _dropless_cfg(**routing)
    lp = _layer0(cfg)
    h = _h(cfg)

    def loss(fn, h, lp):
        y, aux = fn(h, lp)
        return ((y.astype(jnp.float32) ** 2).mean()
                + 0.01 * moe_balance_loss(aux))

    g_ref = jax.jit(jax.grad(lambda h, lp: loss(
        lambda a, b: _moe_ffn(a, b, cfg, None), h, lp), (0, 1)))(h, lp)
    g = jax.jit(jax.grad(lambda h, lp: loss(
        lambda a, b: grouped_moe_ffn(a, b, cfg), h, lp), (0, 1)))(h, lp)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g_ref[0]),
                               rtol=2e-5, atol=2e-6, err_msg="dh")
    for name in g[1]:
        np.testing.assert_allclose(
            np.asarray(g[1][name]), np.asarray(g_ref[1][name]),
            rtol=2e-5, atol=2e-6, err_msg=f"d{name}")


def test_grouped_moe_is_dropless_where_gshard_drops():
    # Tiny capacity forces GShard to drop most overflow tokens; the
    # grouped path must still compute every (token, k) slot.
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False,
                               capacity_factor=0.25)
    lp = _layer0(cfg)
    # Bias the router hard toward expert 0 so overflow is guaranteed.
    lp = dict(lp)
    lp["router"] = lp["router"].at[:, 0].add(10.0)
    h = _h(cfg)
    y_gshard, _ = _moe_ffn(h, lp, cfg, None)
    y_grouped, _ = grouped_moe_ffn(h, lp, cfg)
    # GShard zeroes dropped slots (falls through on the residual);
    # grouped computes them, so some tokens must differ materially.
    diff = np.abs(np.asarray(y_grouped) - np.asarray(y_gshard)).max(-1)
    assert (diff > 1e-3).any(), "expected dropped tokens to differ"
    # And every grouped token got SOME expert output (dropless).
    assert (np.abs(np.asarray(y_grouped)).max(-1) > 1e-6).all()


def test_llama_forward_grouped_impl_end_to_end():
    # moe_impl="auto" with no mesh resolves to the grouped path; the
    # full forward + loss must be finite and trainable. By the EAGER
    # call of ``llama_loss``'s gradient, which users make too: this
    # file's one case that runs the model a primitive at a time.
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False)
    assert cfg.moe_impl == "auto"
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    loss, grads = jax.value_and_grad(llama_loss)(params, batch, cfg)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # The expert weights receive gradient (routing engaged).
    assert float(jnp.abs(grads["layers"]["moe_down"]).max()) > 0


def test_bwd_tilings_clamp_per_direction():
    """Each backward matmul's tiling clamps against ITS OWN problem
    dims, not the forward's (ADVICE r5): the dlhs gmm (transpose_rhs)
    reads its (m, contraction, out) as (m, n, k) — contraction over the
    forward's OUTPUT dim n, output over the forward's contraction k —
    while tgmm's dims coincide with the forward's (m, k, n)."""
    from horovod_tpu.ops.grouped_moe import _bwd_tilings

    # d_model(k)=512 < 1024 <= d_ff(n)=2048 — the straddling shape that
    # mis-clamped before: the old forward-dims clamp gave dlhs a
    # contraction tile of 512 (under its real 2048) and an output tile
    # of 1024 (OVER its real 512-wide output).
    dlhs, tgmm = _bwd_tilings(4096, 512, 2048)
    assert dlhs == (512, 1024, 512), dlhs   # (m, n=2048->1024, k=512)
    assert tgmm == (512, 512, 1024), tgmm   # (m, k=512, n=2048->1024)

    # Small-everything shapes clamp every direction to the problem.
    dlhs, tgmm = _bwd_tilings(256, 128, 64)
    assert dlhs == (256, 64, 128), dlhs
    assert tgmm == (256, 128, 64), tgmm

    # Large square shapes sit at the swept optimum in all directions.
    dlhs, tgmm = _bwd_tilings(16384, 2048, 4096)
    assert dlhs == (512, 1024, 1024), dlhs
    assert tgmm == (512, 1024, 1024), tgmm


def _slots(case, E=8):
    """Routed expert of every (token, k) slot, [S*K] int32."""
    rng = np.random.RandomState(0)
    return jnp.asarray({
        # experts 1, 4 and 7 get nothing
        "empty-experts": rng.choice([0, 2, 3, 5, 6], 96),
        "one-expert": np.full(64, 5),
        # 7 tokens x top-3: S and S*K no power of two
        "s-not-a-power-of-two": rng.randint(0, E, 21),
        "uniform": rng.randint(0, E, 256),
    }[case], jnp.int32)


SLOT_CASES = ["empty-experts", "one-expert", "s-not-a-power-of-two",
              "uniform"]


@pytest.mark.parametrize("case", SLOT_CASES)
def test_group_sizes_equal_bincount(case):
    e_flat = _slots(case)
    got = grouped_moe._group_sizes(e_flat, 8)
    assert got.dtype == jnp.int32 and got.shape == (8,)
    np.testing.assert_array_equal(
        got, np.bincount(np.asarray(e_flat), minlength=8))


def _sorted(e_flat):
    w = jnp.arange(e_flat.size, dtype=jnp.float32)
    (order, inv), w_sorted = grouped_moe._sort_slots(e_flat, w)
    return order, inv, w_sorted


@pytest.mark.parametrize("case", SLOT_CASES)
def test_sorted_order_and_the_inverse_handed_to_the_vjps(case):
    e_flat = _slots(case)
    order, inv, w_sorted = _sorted(e_flat)
    assert order.dtype == inv.dtype == jnp.int32
    # stable: experts ascending, token order within an expert
    np.testing.assert_array_equal(
        order, np.argsort(np.asarray(e_flat), kind="stable"))
    np.testing.assert_array_equal(inv, np.argsort(np.asarray(order)))
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inv)],
                                  np.arange(e_flat.size))
    # the weights rode the sort, and their VJP is the gather by inv
    np.testing.assert_array_equal(w_sorted, np.asarray(order))
    w = jax.random.normal(jax.random.PRNGKey(2), (e_flat.size,))
    ws, pull = jax.vjp(lambda w: grouped_moe._sort_slots(e_flat, w)[1], w)
    ws_ref, pull_ref = jax.vjp(lambda w: w[order], w)
    np.testing.assert_array_equal(ws, ws_ref)
    np.testing.assert_array_equal(pull(w)[0], pull_ref(w)[0])


@pytest.mark.parametrize("case", SLOT_CASES)
def test_dispatch_and_combine_are_each_others_vjp(case):
    """Against plain indexing under autodiff (whose VJPs are scatter-
    adds): every token repeats K times in ``tok``, experts tie."""
    K = 3 if case == "s-not-a-power-of-two" else 4
    e_flat = _slots(case)
    S, D = e_flat.size // K, 16
    order, inv, _ = _sorted(e_flat)
    tok, inv = order // K, inv.reshape(S, K)
    h = jax.random.normal(jax.random.PRNGKey(0), (S, D))
    z = jax.random.normal(jax.random.PRNGKey(1), (S * K, D))

    x, pull = jax.vjp(lambda h: grouped_moe._dispatch(h, tok, inv), h)
    x_ref, pull_ref = jax.vjp(lambda h: h[tok], h)
    np.testing.assert_array_equal(x, x_ref)
    np.testing.assert_allclose(pull(z)[0], pull_ref(z)[0], rtol=1e-6,
                               atol=1e-6)

    y, pull = jax.vjp(lambda z: grouped_moe._combine(z, tok, inv), z)
    y_ref, pull_ref = jax.vjp(
        lambda z: z[inv.reshape(-1)].reshape(S, K, D).sum(1), z)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(pull(h)[0], pull_ref(h)[0])


def test_combine_accumulates_the_k_rows_in_float32():
    # 1 + 8 * 2**-9 is 1.015625 in float32 and, added one bf16 rounding
    # at a time, still 1.0.
    z = jnp.asarray([[1.0]] + [[2.0 ** -9]] * 8, jnp.bfloat16)
    inv = jnp.arange(9, dtype=jnp.int32).reshape(1, 9)
    y = grouped_moe._combine(z, jnp.zeros(9, jnp.int32), inv)
    assert y.dtype == jnp.bfloat16
    assert float(y[0, 0]) == 1.015625


# The share's row movement (``_dispatch_held`` / ``_combine_held``): a
# chunk of R = 64 sorted slots, gathered in blocks of B = 16. ``n`` held
# rows in all, the chunk begins at sorted slot ``start``: it holds
# ``clip(n - start, 0, R)`` of them.
_B, _R = 16, 64
HELD_CASES = [(0, 0), (1, 0), (_B - 1, 0), (_B, 0), (_B + 1, 0),
              (_R - 1, 0), (_R, 0),
              # a later chunk: rows stop before it, inside it, beyond it
              (_R - 1, _R), (_R + _B + 1, _R), (3 * _R, _R)]


@pytest.mark.parametrize("n,start", HELD_CASES)
def test_the_shares_rows_move_as_the_one_shot_forms_move_them(
        n, start, monkeypatch):
    """The gathers block by block and the sums against the one-shot
    forms kept here (the bare gather of R rows; a scatter-add of R
    masked rows), values and both VJPs. Every row of the inputs from the
    chunk's last held row on is NaN: one that reaches a sum fails the
    comparison."""
    monkeypatch.setattr(grouped_moe, "_HELD_BLOCK", _B)
    S, D = 24, 8
    ks = jax.random.split(jax.random.PRNGKey(n + start), 3)
    held = int(np.clip(n - start, 0, _R))
    valid = (jnp.arange(_R) < held)[:, None]
    tok = jax.random.randint(ks[0], (_R,), 0, S)
    h = jax.random.normal(ks[1], (S, D))
    z = jnp.where(valid, jax.random.normal(ks[2], (_R, D)), jnp.nan)

    def one_shot_sum(z):
        return jnp.zeros((S, D)).at[tok].add(jnp.where(valid, z, 0))

    @jax.jit
    def blockwise(h, z, held):
        x, pull_x = jax.vjp(
            lambda h: grouped_moe._dispatch_held(h, tok, held), h)
        y, pull_y = jax.vjp(
            lambda z: grouped_moe._combine_held(z, tok, held, S), z)
        return x, pull_x(z)[0], y, pull_y(h)[0]

    x, dh, y, dz = blockwise(h, z, jnp.int32(held))
    # rows from ``held`` on are nobody's: no group covers them
    np.testing.assert_array_equal(x[:held], grouped_moe._rows(h, tok)[:held])
    np.testing.assert_array_equal(dz[:held], grouped_moe._rows(h, tok)[:held])
    np.testing.assert_allclose(dh, one_shot_sum(z), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, one_shot_sum(z), rtol=1e-6, atol=1e-6)
    assert held > 0 or not (np.any(dh) or np.any(y))


@pytest.mark.parametrize("n,start,blocks", [
    (0, 0, 0), (1, 0, 1), (2048, 0, 1), (2049, 0, 2), (16384, 0, 8),
    (16385, 0, 9), (32768, 0, 16), (40000, 0, 16),        # beyond it
    (32768, 32768, 0), (32769, 32768, 1), (131072, 65536, 16)])
def test_held_blocks_is_the_trip_count_of_the_gathers(n, start, blocks):
    """``ceil(clip(n - start, 0, R) / B)`` at the edges, at the cells'
    R = 32,768 and B = 2,048: none, a row, exactly a block, a full
    chunk, beyond it, and the same in a later chunk."""
    got = grouped_moe.held_blocks(jnp.int32(n), start, 32768, 2048)
    assert got.dtype == jnp.int32 and int(got) == blocks
    assert blocks == -(-min(max(n - start, 0), 32768) // 2048)


@pytest.mark.parametrize("chunks", [2, 4, 8])
@pytest.mark.parametrize("n", ["none", "a row", "a chunk", "a chunk and a row",
                               "two chunks", "all"])
def test_held_chunks_is_the_trip_count_of_the_chunk_loop(n, chunks):
    """``clip(ceil(n / R), 1, chunks)`` at the edges, at the cells' 2
    (LFM2, R = 32,768), 4 (Trinity-Mini, 32,768) and 8 chunks
    (Qwen3-Next, 20,480): no row and one row run the first chunk alone,
    as a full chunk does; one row more reaches the second; every slot
    held runs them all. The later chunks' loop runs from 1 to it."""
    R = 20480 if chunks == 8 else 32768
    n, ran = {"none": (0, 1), "a row": (1, 1), "a chunk": (R, 1),
              "a chunk and a row": (R + 1, 2), "two chunks": (2 * R, 2),
              "all": (chunks * R, chunks)}[n]
    got = grouped_moe.held_chunks(jnp.int32(n), R, chunks)
    assert got.dtype == jnp.int32 and int(got) == ran
    assert ran == min(max(-(-n // R), 1), chunks)
    # every held row lies in a chunk that runs, and the last one that
    # runs holds one (but the first, which always runs)
    assert n <= ran * R and (ran == 1 or n > (ran - 1) * R)


def test_a_chunk_the_block_does_not_divide_is_gathered_as_one_block():
    assert grouped_moe._HELD_BLOCK == 2048 and 32768 % 2048 == 0
    assert grouped_moe._block_rows(32768) == 2048
    assert grouped_moe._block_rows(3000) == 3000


def test_rows_lowers_to_a_bare_gather():
    """No select over the gathered rows: ``jnp.take``'s default
    ``mode="fill"`` masks the result against NaN. (The whole row
    movement, forward and backward, as the v5e's compiler emits it:
    ``test_chip_compile.py``.)"""
    idx = _slots("uniform") // 4
    D = 16
    h = jnp.ones((idx.size // 4, D), jnp.bfloat16)

    def selects_over_rows(text):
        return [ln for ln in text.splitlines()
                if "stablehlo.select" in ln and f"x{D}xbf16" in ln]

    filled = jax.jit(lambda x, i: jnp.take(x, i, axis=0)).lower(
        h, idx).as_text()
    assert selects_over_rows(filled)           # what the check can see
    text = jax.jit(grouped_moe._rows).lower(h, idx).as_text()
    assert text.count('"stablehlo.gather"(') == 1
    assert not selects_over_rows(text)


@pytest.mark.parametrize("remat,sorts", [("attn", 4), ("attn+moe", 2),
                                         ("moe", 2)])
def test_remat_modes_that_save_the_order_sort_nothing_in_backward(
        remat, sorts):
    # One layer: a grouped expert stack runs unrolled (PR 33), so the
    # program holds a layer body a layer; the counts are a body's.
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=remat, n_layers=1)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    text = jax.jit(jax.grad(lambda p: llama_loss(p, batch, cfg))).lower(
        params).as_text()
    assert text.count("stablehlo.sort") == sorts
