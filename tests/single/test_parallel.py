"""SPMD layer tests on the 8-virtual-device CPU mesh (conftest sets
xla_force_host_platform_device_count=8 — the driver's dryrun substrate).

Reference analog: none (Horovod has no in-graph SPMD); correctness is
asserted against single-device closed forms, in the reference's analytic
spirit (SURVEY.md §4).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import parallel
from horovod_tpu.parallel import blockwise_attention
from horovod_tpu.parallel.sharding import apply_sharding


def test_mesh_creation():
    mesh = parallel.create_mesh(data=2, tensor=4)
    assert mesh.shape["data"] == 2
    assert mesh.shape["tensor"] == 4
    assert mesh.shape["pipe"] == 1

    mesh = parallel.create_mesh()  # all devices on data
    assert mesh.shape["data"] == 8

    with pytest.raises(ValueError):
        parallel.create_mesh(data=3, tensor=4)  # 12 != 8


def test_in_graph_collectives():
    mesh = parallel.create_mesh(data=8)

    @jax.shard_map(mesh=mesh, in_specs=P("data"), out_specs=P())
    def summed(x):
        return parallel.psum(jnp.sum(x, keepdims=True), "data")

    x = jnp.arange(16.0)
    np.testing.assert_allclose(np.asarray(summed(x))[0], x.sum())

    @jax.shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def rotated(x):
        return parallel.ppermute_ring(x, "data", shift=1)

    r = np.asarray(rotated(jnp.arange(8.0)))
    np.testing.assert_allclose(r, np.roll(np.arange(8.0), 1))

    @jax.shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def bcast(x):
        return parallel.pbroadcast(x, "data", root=3)

    np.testing.assert_allclose(np.asarray(bcast(jnp.arange(8.0))), 3.0)


def _reference_attention(q, k, v, causal):
    nrep = q.shape[2] // k.shape[2]
    k = np.repeat(np.asarray(k), nrep, axis=2)
    v = np.repeat(np.asarray(v), nrep, axis=2)
    q, k, v = map(lambda t: np.asarray(t, np.float64), (q, k, v))
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = np.arange(tk)[None, :] <= np.arange(tq)[:, None]
        s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_blockwise_attention_matches_reference(causal, kv_heads):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 16, kv_heads, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 16, kv_heads, 8), jnp.float32)
    out = blockwise_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out),
                               _reference_attention(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq_size", [4, 8])
def test_ring_attention_exact(causal, seq_size):
    """Compiled once under ``jax.jit``; the case ``[4-False]`` keeps the
    EAGER call, which users make too (eager, jax compiles the ring a
    primitive at a time: 23 to 50 s a case where the jitted one takes
    three)."""
    mesh = parallel.create_mesh(data=8 // seq_size, seq=seq_size)
    rng = np.random.RandomState(1)
    b, t, h, hkv, d = 2, 32, 4, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)

    ring = functools.partial(parallel.ring_self_attention, mesh=mesh,
                             causal=causal)
    if (seq_size, causal) != (4, False):
        ring = jax.jit(ring)
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               _reference_attention(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients_match():
    mesh = parallel.create_mesh(data=2, seq=4)
    rng = np.random.RandomState(2)
    b, t, h, d = 2, 16, 2, 4
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(parallel.ring_self_attention(q, k, v, mesh) ** 2)

    def loss_plain(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_plain = jax.jit(jax.grad(loss_plain, argnums=(0, 1, 2)))(q, k, v)
    for gr, gp in zip(g_ring, g_plain):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                                   rtol=1e-4, atol=1e-4)


def test_shard_params_rules():
    mesh = parallel.create_mesh(data=2, tensor=4)
    params = {"layer0": {"wq": jnp.zeros((8, 8)), "bias": jnp.zeros(8)},
              "embed": jnp.zeros((16, 8))}
    rules = [
        (r"wq", P(None, "tensor")),
        (r"embed", P("tensor", None)),
    ]
    sh = parallel.shard_params(params, mesh, rules)
    assert sh["layer0"]["wq"].spec == P(None, "tensor")
    assert sh["layer0"]["bias"].spec == P()
    assert sh["embed"].spec == P("tensor", None)
    placed = apply_sharding(params, sh)
    assert placed["embed"].sharding.spec == P("tensor", None)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq_size", [2, 4])
def test_ulysses_attention_exact(causal, seq_size):
    """All-to-all sequence parallelism matches single-device attention,
    including grouped-query K/V with head counts that don't divide the
    axis (replicated internally)."""
    mesh = parallel.create_mesh(data=8 // seq_size, seq=seq_size)
    rng = np.random.RandomState(3)
    b, t, h, hkv, d = 4, 32, 4, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)

    out = parallel.ulysses_self_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out),
                               _reference_attention(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_gradients_match():
    mesh = parallel.create_mesh(data=2, seq=4)
    rng = np.random.RandomState(4)
    b, t, h, d = 2, 16, 4, 8
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss_uly(q, k, v):
        return jnp.sum(parallel.ulysses_self_attention(q, k, v, mesh) ** 2)

    def loss_plain(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v) ** 2)

    g_u = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g_p = jax.jit(jax.grad(loss_plain, argnums=(0, 1, 2)))(q, k, v)
    for gu, gp in zip(g_u, g_p):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gp),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_lcm_replication():
    """Hkv % P != 0 with lcm(Hkv, P) < H: K/V replicate only to the lcm
    and the result still matches the reference."""
    mesh = parallel.create_mesh(data=2, seq=4)
    rng = np.random.RandomState(6)
    b, t, h, hkv, d = 2, 32, 8, 2, 8  # lcm(2, 4) = 4 < 8 = H
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, d), jnp.float32)

    out = parallel.ulysses_self_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               _reference_attention(q, k, v, True),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = parallel.create_mesh(data=1, seq=8)
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 16, 4, 8), jnp.float32)  # 4 heads, P=8
    with pytest.raises(Exception, match="divisible|ring_attention"):
        parallel.ulysses_self_attention(q, q, q, mesh)
