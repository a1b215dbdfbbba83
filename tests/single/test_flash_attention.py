"""Pallas flash-attention kernels in interpret mode — the only CI
coverage the TPU code paths (incl. the bias branches) get without a
chip. Values AND grads compare against reference-math attention.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The package re-exports the flash_attention FUNCTION under the same
# name, shadowing the submodule attribute — resolve the module directly.
fa_mod = importlib.import_module("horovod_tpu.ops.flash_attention")


@pytest.fixture(autouse=True)
def _interpret_mode():
    fa_mod._INTERPRET = True
    yield
    fa_mod._INTERPRET = False


def _qkv(seed=0, B=1, T=32, H=2, D=8):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, T, D)  # kernel layout
    return (jax.random.normal(k1, shape, jnp.float32),
            jax.random.normal(k2, shape, jnp.float32),
            jax.random.normal(k3, shape, jnp.float32))


def _grad(*args, **kwargs):
    """``jax.grad``, compiled: evaluated eagerly, the reference math is
    a compile a primitive, four times what its kernel's case costs."""
    return jax.jit(jax.grad(*args, **kwargs))


def _out_and_grads(fn, w, *operands):
    """``fn(*operands)`` and the gradients of ``sum(fn * w)`` in the
    operands, one compiled program: a kernel's forward under
    ``jax.grad`` is the call it makes alone (``_flash`` and
    ``_flash_fwd`` both run ``_flash_fwd_impl``), so one interpreted
    forward serves the values and the gradients."""
    def loss(*a):
        out = fn(*a)
        return (out * w).sum(), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(len(operands))), has_aux=True))(*operands)
    return out, grads


@functools.partial(jax.jit, static_argnames="causal")
def _ref(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    if bias is not None:
        s = s + bias[:, None, :, :]  # [B,1,1,T] -> broadcast
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_kernel_matches_reference(causal):
    q, k, v = _qkv()
    out = fa_mod._flash(q, k, v, causal, 16, 16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(q, k, v, causal=causal)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_biased_kernel_matches_reference(causal):
    q, k, v = _qkv()
    B, T = q.shape[0], q.shape[2]
    mask = jnp.ones((B, T)).at[:, T - 10:].set(0)
    bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)[:, None, :]
    out = fa_mod._flash_biased(q, k, v, bias, causal, 16, 16)
    ref = _ref(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_biased_kernel_grads_match_reference():
    q, k, v = _qkv()
    B, T = q.shape[0], q.shape[2]
    bias = jnp.where(jnp.arange(T) < T - 10, 0.0,
                     -1e30).astype(jnp.float32)[None, None, :]
    bias = jnp.broadcast_to(bias, (B, 1, T))

    def f(q, k, v):
        return (fa_mod._flash_biased(q, k, v, bias, False, 16, 16) ** 2).sum()

    def fr(q, k, v):
        return (_ref(q, k, v, bias=bias) ** 2).sum()

    g = _grad(f, (0, 1, 2))(q, k, v)
    gr = _grad(fr, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_kernel_values_and_grads(causal):
    """GQA-native kernels (k/v at Hkv heads, indexed hi // n_rep in the
    block specs — no repeat materialization): values and all three
    grads must match reference attention over explicitly repeated
    heads."""
    B, T, H, HKV, D = 1, 32, 4, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (B, H, T, D), jnp.float32)
    k = jax.random.normal(k2, (B, HKV, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, HKV, T, D), jnp.float32)

    def rep(x):  # [B,HKV,T,D] -> [B,H,T,D], blocked head order
        return jnp.broadcast_to(
            x[:, :, None], (B, HKV, H // HKV, T, D)).reshape(B, H, T, D)

    out = fa_mod._flash(q, k, v, causal, 16, 16)
    ref = _ref(q, rep(k), rep(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def f(q, k, v):
        return (fa_mod._flash(q, k, v, causal, 16, 16) ** 2).sum()

    def fr(q, k, v):
        return (_ref(q, rep(k), rep(v), causal=causal) ** 2).sum()

    g = _grad(f, (0, 1, 2))(q, k, v)
    gr = _grad(fr, (0, 1, 2))(q, k, v)
    for a, b_, name in zip(g, gr, "qkv"):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_fully_masked_row_stays_finite():
    q, k, v = _qkv()
    B, T = q.shape[0], q.shape[2]
    bias = jnp.full((B, 1, T), -1e30, jnp.float32)  # every key masked
    out = fa_mod._flash_biased(q, k, v, bias, False, 16, 16)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("D", [8, 64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_chunked_offsets_kernel_matches_reference(causal, D):
    """flash_attention_chunk with dynamic global offsets (the ring-step
    kernel): two chunks merged by logsumexp must equal one full-width
    attention — values and grads (each chunk's lse, f32 [B, H, T],
    carries a cotangent through the merge)."""
    B, T, H = 1, 32, 2
    q, k, v = _qkv(seed=5, B=B, T=T, H=H, D=D)
    half = T // 2

    def merged(q, k, v):
        o, lse = [], []
        for j, kv0 in ((0, 0), (1, half)):
            ob, lb = fa_mod.flash_attention_chunk(
                q, k[:, :, kv0:kv0 + half], v[:, :, kv0:kv0 + half],
                q_offset=0, kv_offset=kv0, causal=causal,
                block_q=16, block_k=16)
            o.append(ob.astype(jnp.float32))
            lse.append(lb)
        assert lse[0].shape == q.shape[:3]     # f32 [B, H, T]
        new = jnp.logaddexp(lse[0], lse[1])
        return (jnp.exp(lse[0] - new)[..., None] * o[0]
                + jnp.exp(lse[1] - new)[..., None] * o[1])

    out = jax.jit(merged)(q, k, v)
    ref = _ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g = _grad(lambda *a: (merged(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    gr = _grad(lambda *a: (_ref(*a, causal=causal) ** 2).sum(),
               (0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_path_matches_blockwise(causal):
    """The flash ring path (pallas chunk kernel + logsumexp merge,
    interpret mode) must match the XLA blockwise ring on a real
    sharded mesh — values and grads, including GQA kv heads. Jitted,
    but for the forward call of ``[False]``, which stays eager."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ring_attention import ring_attention

    n_dev = 2
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    B, T, H, HKV, D = 1, 64, 2, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, HKV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, HKV, D), jnp.float32)
    spec = P(None, "seq", None, None)

    def run(use_flash):
        @jax.shard_map(mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        def _r(ql, kl, vl):
            return ring_attention(ql, kl, vl, "seq", causal=causal,
                                  use_flash=use_flash)

        return _r

    # Compiled once; [False] keeps the forward call EAGER, which users
    # make too (a primitive a compile: the gradients alone took 90 s
    # of a case that way).
    once = jax.jit if causal else (lambda f: f)
    out_flash = once(run(True))(q, k, v)
    out_block = once(run(False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_block),
                               rtol=2e-4, atol=2e-4)

    gf = jax.jit(jax.grad(lambda *a: (run(True)(*a) ** 2).sum(),
                          (0, 1, 2)))(q, k, v)
    gb = jax.jit(jax.grad(lambda *a: (run(False)(*a) ** 2).sum(),
                          (0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gb, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_path_matches_blockwise(causal):
    """Ulysses' post-all-to-all local attention through the pallas
    kernels (interpret) must match its blockwise path — incl. the GQA
    grouping that survives the head split."""
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ulysses import ulysses_attention

    n_dev = 2
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    B, T, H, HKV, D = 1, 64, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, HKV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, HKV, D), jnp.float32)
    spec = P(None, "seq", None, None)

    def run(use_flash):
        @jax.shard_map(mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        def _r(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, "seq", causal=causal,
                                     use_flash=use_flash)

        return _r

    np.testing.assert_allclose(np.asarray(run(True)(q, k, v)),
                               np.asarray(run(False)(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_public_api_mask_via_fallback():
    # flash_attention() with kv_bias through the public API (framework
    # [B,T,H,D] layout); under the _INTERPRET fixture this drives the
    # biased pallas kernel on CPU (without it, the XLA fallback — same
    # math either way).
    B, T, H, D = 2, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
               for kk in ks)
    mask = jnp.ones((B, T)).at[1, 10:].set(0)
    bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)
    out = fa_mod.flash_attention(q, k, v, causal=False, kv_bias=bias)
    ref = _ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
               v.transpose(0, 2, 1, 3), bias=bias[:, None, :])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               rtol=2e-4, atol=2e-4)


def test_zero_valid_key_rows_zero_output_and_grads():
    """q rows with zero valid keys INSIDE a causally-relevant block
    (kv chunk starts mid-q-block) used to emit mean-of-V rows in the
    forward (m stuck at _NEG -> p uniform) and leak spurious dq/dk/dv
    in the backward (lse ~ _NEG makes exp(s - lse) round to 1). Both
    must be exactly zero so a standalone chunk is correct in its own
    right, not just after logsumexp merging."""
    q, k, v = _qkv(seed=7, T=16)
    # block_q=16 spans all queries; kv chunk starts at global 8, so
    # rows 0..7 have zero valid keys inside a relevant block (row 8
    # attends to one key, etc.) — the whole-block skip does NOT fire.
    def run(qq, kk, vv):
        return fa_mod.flash_attention_chunk(
            qq, kk, vv, q_offset=0, kv_offset=8, causal=True,
            block_q=16, block_k=16)

    o, lse = run(q, k, v)
    np.testing.assert_array_equal(np.asarray(o[:, :, :8]), 0.0)
    assert np.all(np.asarray(lse[:, :, :8]) < -1e29)
    # Rows with valid keys must be untouched by the guard.
    assert np.all(np.abs(np.asarray(o[:, :, 8:])) > 0)

    # Cotangent ONLY on the fully-masked rows: every gradient must be
    # exactly zero (pre-fix: dv max ~8, dq max ~6).
    def loss(qq, kk, vv):
        oo, _ = run(qq, kk, vv)
        return (oo[:, :, :8] ** 2).sum() + oo[:, :, :8].sum()

    dq, dk, dv = _grad(loss, (0, 1, 2))(q, k, v)
    for g, name in zip((dq, dk, dv), "qkv"):
        np.testing.assert_array_equal(
            np.asarray(g), 0.0, err_msg=f"d{name} leaked")


# --- PR 28: one-pass backward, three kinds of tile ----------------------
# Every case below runs at 3 x 3 tiles or more, so that skipped, interior
# and straddling tiles all occur, and compares values and all three
# gradients with reference math.

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6),
                   static_argnames=("causal", "window"))
def _chunk_ref(q, k, v, q_off, kv_off, causal=True, window=0, bias=None):
    """Reference for one chunk with GLOBAL positions: (o, lse [B,H,T],
    rows with a valid key [1,1,T]). A row without one has output 0 (its
    lse is not compared: the kernel leaves it at ~-1e30). ``window``: the
    causal band; ``bias``: f32 [B,1,Tk], added per key."""
    d = q.shape[-1]
    n_rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, n_rep, 1), jnp.repeat(v, n_rep, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        i = (q_off + jnp.arange(q.shape[2]))[:, None]
        j = (kv_off + jnp.arange(k.shape[2]))[None, :]
        mask = (i >= j) & (j > i - window) if window else i >= j
    has_key = mask.any(-1)[None, None, :]
    s = jnp.where(mask, s, -1e30)
    if bias is not None:
        s = s + bias[:, None, :, :]
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    lse = jax.nn.logsumexp(s, -1)
    return jnp.where(has_key[..., None], o, 0.0), lse, has_key


def _assert_grads_close(got, want):
    for a, b, name in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32)])
def test_unequal_blocks_values_and_grads(block_q, block_k):
    q, k, v = _qkv(seed=3, T=96)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    assert all(fa_mod.tile_counts(96, 96, block_q, block_k, True))

    out, grads = _out_and_grads(
        lambda *a: fa_mod._flash(*a, True, block_q, block_k), w, q, k, v)
    ref, ref_grads = _out_and_grads(
        lambda *a: _ref(*a, causal=True), w, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    _assert_grads_close(grads, ref_grads)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_four_query_heads_a_kv_head(causal):
    B, T, H, HKV, D = 2, 48, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, HKV, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, HKV, T, D), jnp.float32)
    w = jax.random.normal(ks[3], q.shape)
    assert fa_mod.tile_counts(T, T, 16, 16, causal) == \
        ((3, 3, 3) if causal else (0, 9, 0))

    out, grads = _out_and_grads(
        lambda *a: fa_mod._flash(*a, causal, 16, 16), w, q, k, v)
    ref, ref_grads = _out_and_grads(
        lambda *a: _chunk_ref(*a, 0, 0, causal)[0], w, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    _assert_grads_close(grads, ref_grads)


# (q_offset, kv_offset) of a 48-row chunk against a 48-key chunk at
# 16 x 16 blocks, with the tiles each has (skipped, interior, straddling).
_CHUNKS = {"past": ((96, 0), (0, 9, 0)), "future": ((0, 96), (9, 0, 0)),
           # keys 40..87 under rows 32..79: rows 32..39 have no key at
           # all inside a tile that runs
           "crossing": ((32, 40), (3, 1, 5))}


@pytest.mark.parametrize("chunk", sorted(_CHUNKS))
def test_offset_chunks_with_lse_cotangent(chunk):
    (q_off, kv_off), tiles = _CHUNKS[chunk]
    assert fa_mod.tile_counts(48, 48, 16, 16, True, q_off, kv_off) == tiles
    B, T, H, HKV, D = 1, 48, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, HKV, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, HKV, T, D), jnp.float32)
    w = jax.random.normal(ks[3], q.shape)
    u = jax.random.normal(ks[4], (B, H, T))
    has_key = _chunk_ref(q, k, v, q_off, kv_off)[2]

    def run(*a):
        # traced offsets, as a ring step passes them
        return jax.jit(lambda qo, ko: fa_mod.flash_attention_chunk(
            *a, qo, ko, causal=True, block_q=16, block_k=16))(q_off, kv_off)

    def loss(fn):
        def _l(*a):
            o, lse = fn(*a)[:2]
            return (o * w).sum() + (jnp.where(has_key, lse, 0.0) * u).sum()
        return _l

    o, lse = run(q, k, v)
    o_ref, lse_ref, _ = _chunk_ref(q, k, v, q_off, kv_off)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(jnp.where(has_key, lse, 0.0)),
        np.asarray(jnp.where(has_key, lse_ref, 0.0)), rtol=2e-4, atol=2e-4)
    assert np.all(np.asarray(lse)[~np.broadcast_to(has_key, lse.shape)]
                  < -1e29)
    got = _grad(loss(run), (0, 1, 2))(q, k, v)
    want = _grad(loss(lambda *a: _chunk_ref(*a, q_off, kv_off)),
                 (0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)
    if chunk == "future":
        for g in got:
            np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_bias_with_a_fully_padded_batch_row(causal):
    """Batch row 0 has its last 20 keys padded, batch row 1 every key:
    row 1's output and every gradient through it are exactly zero, row 0
    matches the reference."""
    B, T = 2, 48
    q, k, v = _qkv(seed=12, B=B, T=T)
    w = jax.random.normal(jax.random.PRNGKey(13), q.shape)
    pad = jnp.stack([jnp.arange(T) >= T - 20, jnp.ones(T, bool)])
    bias = jnp.where(pad, -1e30, 0.0).astype(jnp.float32)[:, None, :]
    live = jnp.array([1.0, 0.0])[:, None, None, None]

    def f(*a):
        return (fa_mod._flash_biased(*a, bias, causal, 16, 16) * w).sum()

    def fr(*a):
        return (_ref(*a, bias=bias, causal=causal) * live * w).sum()

    out = fa_mod._flash_biased(q, k, v, bias, causal, 16, 16)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.asarray(_ref(q, k, v, bias=bias, causal=causal)[0]),
        rtol=2e-4, atol=2e-4)
    got = _grad(f, (0, 1, 2))(q, k, v)
    _assert_grads_close(got, _grad(fr, (0, 1, 2))(q, k, v))
    for g in got:
        np.testing.assert_array_equal(np.asarray(g[1]), 0.0)


def _brute_force_tiles(t, tk, bq, bk, causal, q_off, kv_off):
    mask = np.ones((t, tk), bool)
    if causal:
        mask = (q_off + np.arange(t))[:, None] >= (kv_off + np.arange(tk))
    tiles = mask.reshape(t // bq, bq, tk // bk, bk).transpose(0, 2, 1, 3)
    full, some = tiles.all((2, 3)), tiles.any((2, 3))
    return ~some, full, some & ~full  # skipped, interior, straddling


_GRIDS = [
    # t, tk, block_q, block_k, causal, q_offset, kv_offset
    (4096, 4096, 1024, 1024, True, 0, 0),
    (8192, 8192, 1024, 1024, True, 0, 0),
    (4096, 4096, 1024, 1024, False, 0, 0),
    (96, 96, 32, 16, True, 0, 0),
    (96, 96, 16, 32, True, 0, 0),
    (48, 48, 16, 16, True, 96, 0),
    (48, 48, 16, 16, True, 0, 96),
    (48, 48, 16, 16, True, 32, 40),
    (48, 96, 16, 32, True, 50, 7),
    (64, 32, 16, 16, True, 15, 16),
]


@pytest.mark.parametrize("grid", _GRIDS, ids=lambda g: "-".join(map(str, g)))
def test_tile_counts_match_a_brute_force_mask(grid):
    kinds = _brute_force_tiles(*grid)
    assert fa_mod.tile_counts(*grid) == tuple(int(x.sum()) for x in kinds)


def test_tile_counts_quoted_in_the_docs():
    assert fa_mod.tile_counts(4096, 4096, 1024, 1024, True) == (6, 6, 4)
    assert fa_mod.tile_counts(8192, 8192, 1024, 1024, True) == (28, 28, 8)
    assert fa_mod.tile_counts(4096, 4096, 1024, 1024, False) == (0, 16, 0)


@pytest.mark.parametrize("grid", [g for g in _GRIDS if g[4]],
                         ids=lambda g: "-".join(map(str, g)))
def test_kernel_predicate_with_traced_offsets_matches_the_mask(grid):
    """The predicate the kernels branch on (`_tile_kinds`), fed what a
    kernel feeds it — offsets that are only known at run time plus
    block index times block size — sorts every tile as the mask does."""
    t, tk, bq, bk, causal, q_off, kv_off = grid
    skipped, interior, straddling = _brute_force_tiles(*grid)

    @jax.jit
    def kinds(q_offset, kv_offset):
        iq = jnp.arange(t // bq)[:, None]
        jk = jnp.arange(tk // bk)[None, :]
        return fa_mod._tile_kinds(causal, q_offset + iq * bq, bq,
                                  kv_offset + jk * bk, bk)

    inside, crossing = kinds(jnp.int32(q_off), jnp.int32(kv_off))
    np.testing.assert_array_equal(np.asarray(inside), interior)
    np.testing.assert_array_equal(np.asarray(crossing), straddling)
    assert not np.any(np.asarray(inside & crossing))
    np.testing.assert_array_equal(np.asarray(~(inside | crossing)), skipped)


# case -> (what runs, head width, window). PR 37: heads 64 and 128 wide
# (LFM2's and the other cells'), with and without a window, a bias and
# offsets, each checked down to the statistic the kernels hand over.
_LANE_WIDE = {
    "causal": ("causal", 8, 0), "offsets": ("offsets", 8, 0),
    "bias": ("bias", 8, 0), "unequal": ("causal", 8, 0),
    "causal-d64": ("causal", 64, 0), "causal-d128": ("causal", 128, 0),
    "window-d64": ("causal", 64, 600), "window-d128": ("causal", 128, 600),
    "bias-d64": ("bias", 64, 0), "bias-d128": ("bias", 128, 0),
    "offsets-d64": ("offsets", 64, 0), "offsets-d128": ("offsets", 128, 0),
}


@pytest.mark.parametrize("case", list(_LANE_WIDE))
def test_lane_wide_blocks(case):
    """Blocks of 256 and 512 (whole 128-wide lane tiles, as on the
    chip; the cases above run blocks of 16 and 32): values and gradients
    at 3 x 3 tiles and more, plain causal, with a chunk offset that no
    block boundary meets (and an lse cotangent), with a padded tail,
    with unequal blocks, under a band that no block boundary meets; and
    the log-sum-exp as it crosses the call boundary, f32 [B, H, 1, T]
    (T on the lanes), through ``_flash``'s, ``_flash_biased``'s and
    ``flash_attention_chunk``'s forward."""
    kind, D, window = _LANE_WIDE[case]
    B, H, HKV = 1, 2, 1
    T, bq, bk = (1536, 512, 256) if case == "unequal" else (768, 256, 256)
    if window:   # five blocks: a tile wholly below the band as well
        T = 1280
        assert fa_mod.tile_counts(T, T, bq, bk, True, 0, 0, window) \
            == (11, 4, 10)
    q_off, kv_off = (300, 140) if kind == "offsets" else (0, 0)
    ks = jax.random.split(jax.random.PRNGKey(21), 5)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, HKV, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, HKV, T, D), jnp.float32)
    w = jax.random.normal(ks[3], q.shape)
    u = jax.random.normal(ks[4], (B, H, T))
    assert all(fa_mod.tile_counts(T, T, bq, bk, True, q_off, kv_off,
                                  window))
    bias = None
    if kind == "bias":
        bias = jnp.where(jnp.arange(T) >= T - 200, -1e30,
                         0.0).astype(jnp.float32)[None, None, :]

    def run(*a):
        if kind == "bias":
            return fa_mod._flash_biased(*a, bias, True, bq, bk), None
        if kind == "offsets":
            return jax.jit(lambda qo, ko: fa_mod.flash_attention_chunk(
                *a, qo, ko, causal=True, block_q=bq, block_k=bk))(
                    q_off, kv_off)
        return fa_mod._flash(*a, True, bq, bk, window), None

    def ref(*a):
        return _chunk_ref(*a, q_off, kv_off, window=window, bias=bias)[:2]

    has_key = _chunk_ref(q, k, v, q_off, kv_off)[2]

    def readings(fn):
        """One compiled program a side: the values, the statistic and
        the three gradients."""
        def _l(*a):
            o, lse = fn(*a)
            extra = 0.0 if kind != "offsets" else \
                (jnp.where(has_key, lse, 0.0) * u).sum()
            return (o * w).sum() + extra, (o, lse)
        (_, (o, lse)), grads = jax.jit(jax.value_and_grad(
            _l, (0, 1, 2), has_aux=True))(q, k, v)
        return o, lse, grads

    o, _, grads = readings(run)
    o_ref, lse_ref, ref_grads = readings(ref)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    _assert_grads_close(grads, ref_grads)
    # The statistic the forward hands the backward (the residual named
    # flash_lse) and a ring step its merge.
    offsets = jnp.array([q_off, kv_off], jnp.int32) \
        if kind == "offsets" else None
    lse = fa_mod._flash_fwd_impl(q, k, v, bias, True, bq, bk,
                                 offsets=offsets, window=window)[1]
    assert lse.shape == (B, H, 1, T) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(jnp.where(has_key, lse[:, :, 0], 0.0)),
        np.asarray(jnp.where(has_key, lse_ref, 0.0)),
        rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------
# The causal band (sliding-window attention): key j visible to row i
# where i - W < j <= i.
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=3)
def _band_ref(q, k, v, window):
    """Kernel layout [B, H(kv), T, D], an explicit band mask."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    t = q.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _brute_force_band_tiles(t, bq, bk, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = (j <= i) & (j > i - window)
    tiles = mask.reshape(t // bq, bq, t // bk, bk).transpose(0, 2, 1, 3)
    full, some = tiles.all((2, 3)), tiles.any((2, 3))
    return ~some, full, some & ~full


_BANDS = [
    # t, block_q, block_k, window: smaller than, equal to and larger
    # than a block; no multiple of one; unequal blocks; wider than T.
    (8192, 1024, 1024, 2048), (4096, 1024, 1024, 2048),
    (96, 16, 16, 5), (96, 16, 16, 16), (96, 16, 16, 17), (96, 16, 16, 40),
    (96, 32, 16, 24), (96, 16, 32, 24), (96, 16, 16, 1), (96, 16, 16, 500),
]


@pytest.mark.parametrize("band", _BANDS, ids=lambda g: "-".join(map(str, g)))
def test_tile_counts_with_a_window_match_a_brute_force_band(band):
    t, bq, bk, window = band
    kinds = _brute_force_band_tiles(*band)
    assert fa_mod.tile_counts(t, t, bq, bk, True, window=window) \
        == tuple(int(x.sum()) for x in kinds)
    # the predicate on traced scalars, as a kernel feeds it
    inside, crossing = jax.jit(lambda z: fa_mod._tile_kinds(
        True, z + jnp.arange(t // bq)[:, None] * bq, bq,
        z + jnp.arange(t // bk)[None, :] * bk, bk, window))(jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(inside), kinds[1])
    np.testing.assert_array_equal(np.asarray(crossing), kinds[2])


def test_window_tile_counts_quoted_in_the_docs():
    # 21 tiles run of the 36 a full layer runs at T = 8192
    assert fa_mod.tile_counts(8192, 8192, 1024, 1024, True,
                              window=2048) == (43, 7, 14)
    assert fa_mod.tile_counts(4096, 4096, 1024, 1024, True,
                              window=2048) == (7, 3, 6)


@pytest.mark.parametrize("window", [5, 16, 24, 40, 200])
@pytest.mark.parametrize("t, bq, bk", [(96, 16, 16), (80, 16, 8)])
def test_window_kernel_values_and_grads(t, bq, bk, window):
    """T no power of two (and 80 no multiple of 32, so `_pick_block`
    degrades); W smaller than, equal to and larger than a block, and
    wider than the sequence (= plain causal). GQA, 2 query heads a
    KV head."""
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(ks[0], (2, 4, t, 8), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, t, 8), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, t, 8), jnp.float32)
    w = jax.random.normal(ks[3], q.shape)
    out, got = _out_and_grads(
        lambda *a: fa_mod._flash(*a, True, bq, bk, window), w, q, k, v)
    want, ref = _out_and_grads(
        lambda *a: _band_ref(*a, window), w, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    _assert_grads_close(got, ref)
    if window >= t:   # a window wider than the sequence changes nothing
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(fa_mod._flash(q, k, v, True, bq,
                                                      bk, 0)),
            rtol=1e-6, atol=1e-6)


def test_window_public_api_both_paths_and_its_refusals():
    """[B,T,H,D] layout through ``flash_attention``: the kernel
    (interpret) and the off-TPU ``blockwise_attention`` path agree with
    the explicit band; a window without ``causal`` or with a bias is
    refused."""
    from horovod_tpu.parallel.ring_attention import blockwise_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 72, 4, 8), jnp.float32)
    k = jax.random.normal(ks[1], (1, 72, 2, 8), jnp.float32)
    v = jax.random.normal(ks[2], (1, 72, 2, 8), jnp.float32)
    ref = _band_ref(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                    20).transpose(0, 2, 1, 3)
    got = fa_mod.flash_attention(q, k, v, window=20, block_q=24,
                                 block_k=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(blockwise_attention(q, k, v, window=20)),
        np.asarray(ref), rtol=2e-4, atol=2e-4)
    fa_mod._INTERPRET = False     # the reference branch of the same call
    np.testing.assert_allclose(
        np.asarray(fa_mod.flash_attention(q, k, v, window=20)),
        np.asarray(ref), rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="window"):
        fa_mod.flash_attention(q, k, v, causal=False, window=20)
    with pytest.raises(ValueError, match="window"):
        fa_mod.flash_attention(q, k, v, window=20,
                               kv_bias=jnp.zeros((1, 72)))


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)],
                         ids=["one-device", "data2-tensor2"])
@pytest.mark.parametrize("window", [0, 20])
@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernel", "reference"])
def test_head_major_entry_is_the_public_entry(interpret, window, mesh_shape):
    """``flash_attention_head_major`` on [B, H, T, D] operands (what
    ``ops/qk_prep.py`` writes) gives what ``flash_attention`` gives on
    the same operands as [B, T, H, D], values and gradients, with a
    window and without, on one device and with the kernel sharding
    itself over a mesh; off the TPU it takes the same reference math."""
    mesh = None
    if mesh_shape:
        if len(jax.devices()) < 4:
            pytest.skip("needs four devices")
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(mesh_shape),
            ("data", "tensor"))
    fa_mod._INTERPRET = interpret
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(ks[0], (2, 48, 4, 8), jnp.float32)
    k = jax.random.normal(ks[1], (2, 48, 2, 8), jnp.float32)
    v = jax.random.normal(ks[2], (2, 48, 2, 8), jnp.float32)
    w = jax.random.normal(ks[3], q.shape)
    blocks = dict(block_q=16, block_k=16, window=window, mesh=mesh)

    def public(q, k, v):
        return fa_mod.flash_attention(q, k, v, **blocks)

    def head_major(q, k, v):
        return fa_mod.flash_attention_head_major(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), **blocks)

    def readings(fn):
        def loss(*a):
            out = fn(*a)
            return (out * w).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    ref, ref_grads = readings(public)
    got, got_grads = readings(head_major)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    _assert_grads_close(got_grads, ref_grads)
    with pytest.raises(ValueError, match="window"):
        fa_mod.flash_attention_head_major(q, k, v, causal=False, window=20)


# -- latent attention's call: values another width, the scale handed in


def _latent_operands(B=1, T=64, H=4, dqk=24, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    return (jax.random.normal(ks[0], (B, T, H, dqk), jnp.float32),
            jax.random.normal(ks[1], (B, T, H, dqk), jnp.float32),
            jax.random.normal(ks[2], (B, T, H, dv), jnp.float32),
            jax.random.normal(ks[3], (B, T, H, dv), jnp.float32))


@pytest.mark.parametrize("scale", [None, 1.4159 ** 2 / 24 ** 0.5])
@pytest.mark.parametrize("blocks", [(64, 64), (16, 32)])
def test_values_of_another_width_and_a_scale_handed_in(scale, blocks):
    """Queries and keys one and a half times as wide as the values (the
    published 192 / 128 in small) through the kernels in interpret mode
    against ``blockwise_attention``, forward and the three gradients."""
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.ring_attention import blockwise_attention

    q, k, v, w = _latent_operands()

    def grads(attn, **kw):
        def f(q, k, v):
            out = attn(q, k, v, causal=True, scale=scale, **kw)
            return jnp.sum(out * w), out
        return _grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = grads(flash_attention, block_q=blocks[0], block_k=blocks[1])
    want, out_want = grads(blockwise_attention)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, out_want, rtol=2e-4, atol=2e-4)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)
    # the handed-in scale is no 1 / sqrt(D): leaving it out shows
    if scale is not None:
        plain = flash_attention(q, k, v, causal=True)
        assert float(jnp.abs(plain - out).max()) > 1e-2


def test_one_width_and_no_scale_is_bit_for_bit_what_it_was():
    """The call every other configuration makes: the default scale is
    ``1 / sqrt(D)`` handed in, to the bit, forward and backward."""
    from horovod_tpu.ops import flash_attention

    q, k, _, _ = _latent_operands()
    v = k * 0.5 + 1.0

    def grads(**kw):
        return _grad(lambda q, k, v: jnp.sum(jnp.square(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, **kw))),
            argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(), grads(scale=q.shape[-1] ** -0.5)):
        np.testing.assert_array_equal(a, b)


def test_a_bias_takes_no_scale_and_one_width():
    from horovod_tpu.ops import flash_attention

    q, k, v, _ = _latent_operands()
    bias = jnp.zeros((1, 64), jnp.float32)
    with pytest.raises(ValueError, match="no scale and one width"):
        flash_attention(q, k, v, causal=False, kv_bias=bias)
    with pytest.raises(ValueError, match="no scale and one width"):
        flash_attention(q, k, k, causal=False, kv_bias=bias, scale=0.1)
