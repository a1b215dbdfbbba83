"""ZeRO-1 sharded optimizer (parallel/zero.py + the zero= split step).

Pins, in order of how expensively they were learned:

- the ring segment-ownership rotation helper agrees with the C++
  engine's C ABI AND with a numpy replay of the ring order — the r10
  "(r+1)%N" off-by-one can no longer be re-derived wrong;
- bucket layout: dtype grouping, bucket_bytes chunking, padding to the
  shard count, pack/unpack roundtrip, shard-aligned boundaries;
- pack stays LAYOUT-EXACT for GSPMD-sharded leaves (see
  BucketLayout.pack);
- sharded-vs-replicated parity at N in {2, 4}: grads (via loss),
  params, and optimizer state of the zero split step match the r06
  replicated ``fused_adam`` step and ``optax.adam``, for both the plain
  and fp32-master fused kernels;
- the state's uniform leading-dim divisibility (what makes per-rank
  memory exactly 1/N once laid out over the axis), and the byte
  predictors' exact agreement.

Quick lane; pure CPU; no multi-process ranks (the eager 2-rank lane is
tests/parallel/test_zero_eager.py + ``make zero-smoke``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import zero as Z
from horovod_tpu.parallel.precision import fused_adam, fused_master_adam
from horovod_tpu.parallel.train_step import make_split_train_step

pytestmark = pytest.mark.quick


# ---- segment-ownership rotation --------------------------------------

def _numpy_ring_owned(rank, size, rot):
    """Replay the ring reduce phase and report which segment ended up
    with every rank's contribution at `rank` — the ground truth both
    helpers must match."""
    # seg -> set of contributing ranks, per rank; walk the N-1 steps.
    holders = {r: {s: {r} for s in range(size)} for r in range(size)}
    for step in range(size - 1):
        sends = {}
        for r in range(size):
            seg = (r - step + rot) % size
            sends[(r + 1) % size] = (seg, set(holders[r][seg]))
        for r, (seg, contrib) in sends.items():
            holders[r][seg] |= contrib
    full = [s for s, c in holders[rank].items() if len(c) == size]
    assert len(full) == 1
    return full[0]


@pytest.mark.parametrize("size", [2, 3, 4, 7])
@pytest.mark.parametrize("rot", [0, -1])
def test_ring_owned_segment_matches_ring_replay(size, rot):
    for rank in range(size):
        assert Z.ring_owned_segment(rank, size, rot) == \
            _numpy_ring_owned(rank, size, rot)


def test_ring_owned_segment_known_values():
    # The r10 trap, pinned as literals: allreduce rotation -> (r+1)%N;
    # reduce-scatter rotation -> r itself.
    assert [Z.ring_owned_segment(r, 4) for r in range(4)] == [1, 2, 3, 0]
    assert [Z.ring_owned_segment(r, 4, -1) for r in range(4)] == \
        [0, 1, 2, 3]
    with pytest.raises(ValueError):
        Z.ring_owned_segment(4, 4)


def test_ring_owned_segment_matches_core_c_abi():
    """The Python twin and the engine's own helper must be ONE fact."""
    from horovod_tpu.common.basics import HorovodBasics

    b = HorovodBasics()
    try:
        lib = b.lib
    except OSError:
        pytest.skip("native core not built")
    for size in (2, 3, 4, 5):
        for rank in range(size):
            for rot in (0, -1):
                assert b.ring_owned_segment(rank, size, rot) == \
                    Z.ring_owned_segment(rank, size, rot)
    # send-segment helper: step 0 of the allgather phase (rot=+1 walk)
    # sends exactly the owned segment.
    for size in (2, 4):
        for rank in range(size):
            assert b.ring_send_segment(rank, 0, size, 1) == \
                Z.ring_owned_segment(rank, size)
    assert lib.hvdtpu_ring_owned_segment(9, 4, 0) == -1  # bad rank


# ---- bucket layout ---------------------------------------------------

def _leaves():
    return [jnp.arange(10, dtype=jnp.float32),
            jnp.ones((3, 4), jnp.float32),
            jnp.full((5,), 2, jnp.int32),
            jnp.arange(6, dtype=jnp.float32).reshape(2, 3)]


def test_layout_groups_by_dtype_and_pads_to_shards():
    lay = Z.zero_bucket_layout(_leaves(), n_shards=4,
                               bucket_bytes=1 << 20)
    # f32 leaves (10 + 12 + 6 = 28 elems -> pad 28) and the i32 leaf
    # (5 -> pad 8) land in separate buckets.
    assert len(lay.buckets) == 2
    f32, i32 = lay.buckets
    assert f32.indices == (0, 1, 3) and f32.nelems == 28
    assert f32.padded == 28 and f32.shard_elems(4) == 7
    assert i32.indices == (2,) and i32.padded == 8
    assert i32.shard_elems(4) == 2


def test_layout_bucket_bytes_chunks_and_roundtrip():
    leaves = _leaves()
    lay = Z.zero_bucket_layout(leaves, n_shards=2, bucket_bytes=48)
    # 48-byte buckets split the f32 group: 10*4=40 fits, the next leaf
    # (48 bytes) opens a new bucket, 6*4=24 more closes it at 72>48...
    assert all(b.padded % 2 == 0 for b in lay.buckets)
    packed = lay.pack(leaves)
    assert [p.shape[0] for p in packed] == [b.padded for b in lay.buckets]
    out = lay.unpack(packed)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype and a.shape == b.shape


def test_layout_oversized_single_leaf_gets_one_bucket():
    big = [jnp.ones((1000,), jnp.float32), jnp.ones((3,), jnp.float32)]
    lay = Z.zero_bucket_layout(big, n_shards=4, bucket_bytes=64)
    assert [b.indices for b in lay.buckets] == [(0,), (1,)]
    assert lay.buckets[1].padded == 4  # 3 -> padded to the shard count


def test_shard_boundaries_align_with_buckets():
    """Rank r's shard of every packed bucket is [r*s, (r+1)*s) — the
    rot=-1 ownership — and reassembling shards in rank order IS the
    packed bucket (what the eager allgather does)."""
    leaves = _leaves()
    for n in (2, 4):
        lay = Z.zero_bucket_layout(leaves, n_shards=n,
                                   bucket_bytes=1 << 20)
        for flat in lay.pack(leaves):
            s = flat.shape[0] // n
            shards = [flat[r * s:(r + 1) * s] for r in range(n)]
            np.testing.assert_array_equal(
                np.asarray(jnp.concatenate(shards)), np.asarray(flat))


def test_pack_shard_equals_sliced_pack():
    """The eager lane's direct shard assembly must equal slicing the
    full packed bucket — for every bucket, every rank, at shard counts
    that split leaves mid-way."""
    leaves = _leaves()
    for n in (2, 4):
        lay = Z.zero_bucket_layout(leaves, n_shards=n, bucket_bytes=48)
        packed = lay.pack(leaves)
        for i, b in enumerate(lay.buckets):
            s = b.shard_elems(n)
            for r in range(n):
                np.testing.assert_array_equal(
                    np.asarray(lay.pack_shard(leaves, i, r)),
                    np.asarray(packed[i][r * s:(r + 1) * s]),
                    err_msg=f"bucket {i} rank {r} of {n}")


def test_pack_of_sharded_leaves_is_layout_exact():
    """Pack of an axis-sharded leaf in a subprocess with 4 forced host
    devices, against the unsharded truth (an earlier jax returned the
    physical per-device layout from a jitted concatenate-of-reshape
    here, which is why pack writes update-slices)."""
    import subprocess
    import sys

    code = """
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from horovod_tpu import parallel
from horovod_tpu.parallel import zero as Z
mesh = parallel.create_mesh(devices=jax.devices()[:4], data=2, fsdp=2)
a = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
b = jnp.full((7,), -0.25, jnp.float32)
lay = Z.zero_bucket_layout([a, b], 4, 1 << 20)
a_sh = jax.device_put(a, NamedSharding(mesh, P("fsdp", None)))
packed = jax.jit(lambda x, y: lay.pack([x, y]))(a_sh, b)
ref = np.concatenate([np.arange(64, dtype=np.float32),
                      np.full(7, -0.25, np.float32),
                      np.zeros(1, np.float32)])
np.testing.assert_array_equal(np.asarray(packed[0]), ref)
print("PACK_OK")
"""
    env = dict(__import__("os").environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240,
                         cwd=__import__("os").path.dirname(
                             __import__("os").path.dirname(
                                 __import__("os").path.dirname(
                                     __import__("os").path.abspath(
                                         __file__)))))
    assert out.returncode == 0 and "PACK_OK" in out.stdout, (
        out.stdout[-500:], out.stderr[-1500:])


# ---- sharded-vs-replicated parity ------------------------------------

def _problem():
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (8, 16)) * 0.1,
              "b1": jnp.zeros((13,)),
              "w2": jax.random.normal(jax.random.PRNGKey(1),
                                      (16, 4)) * 0.1}

    def loss_fn(p, d):
        h = jnp.tanh(d["x"] @ p["w1"] + p["b1"][:16].sum())
        return jnp.mean((h @ p["w2"] - d["y"]) ** 2)

    batch = {"x": jax.random.normal(jax.random.PRNGKey(2), (8, 8)),
             "y": jax.random.normal(jax.random.PRNGKey(3), (8, 4))}
    return params, loss_fn, batch


def _copy(t):
    return jax.tree.map(jnp.array, t)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_zero_adam_matches_replicated_and_optax(n_shards):
    """Grad/param/optimizer-state pins: the zero split step == the r06
    replicated fused_adam step == optax.adam, at N in {2, 4} (powers of
    two, so the scatter's x N / N mean roundtrip is EXACT in f32)."""
    import optax

    params, loss_fn, batch = _problem()
    ref = make_split_train_step(loss_fn, fused_adam(1e-2),
                                microbatches=2)
    zts = make_split_train_step(
        loss_fn, fused_adam(1e-2), microbatches=2,
        zero=Z.ZeroConfig(size=n_shards, bucket_bytes=128))
    ots = make_split_train_step(loss_fn, optax.adam(1e-2),
                                microbatches=2)
    rc, zc, oc = (ref.init(_copy(params)), zts.init(_copy(params)),
                  ots.init(_copy(params)))
    for _ in range(3):
        rl, rc = ref.step(rc, batch)
        zl, zc = zts.step(zc, batch)
        ol, oc = ots.step(oc, batch)
    # Loss (same grads — the grad programs are shared code).
    assert float(zl) == pytest.approx(float(rl), abs=1e-7)
    assert float(zl) == pytest.approx(float(ol), rel=1e-6)
    # Params: zero == replicated fused == optax.
    for a, b in zip(jax.tree.leaves(rc[0]), jax.tree.leaves(zc[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(oc[0]), jax.tree.leaves(zc[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # Optimizer state: the zero mu/nu are the PACKED replicated moments.
    layout = Z.zero_bucket_layout(jax.tree.leaves(params), n_shards, 128)
    rmu_packed = layout.pack(jax.tree.leaves(rc[1].mu))
    for packed, z in zip(rmu_packed, zc[1].mu):
        np.testing.assert_allclose(np.asarray(packed), np.asarray(z),
                                   rtol=2e-6, atol=1e-7)
    assert int(zc[1].count[0]) == 3
    # Uniform shardability: every state leaf splits exactly N ways.
    for leaf in jax.tree.leaves(zc[1]):
        assert leaf.shape[0] % n_shards == 0


def test_zero_master_adam_matches_replicated_master():
    """The fp32-master variant: sharded master/moments, compute-dtype
    carry — must match the replicated fused_master_adam step."""
    params, loss_fn, batch = _problem()
    mk = lambda **kw: make_split_train_step(  # noqa: E731
        loss_fn, fused_master_adam(1e-2, compute_dtype=jnp.float32),
        microbatches=1, **kw)
    ref, zts = mk(), mk(zero=Z.ZeroConfig(size=2, bucket_bytes=1 << 20))
    rc, zc = ref.init(_copy(params)), zts.init(_copy(params))
    for _ in range(2):
        rl, rc = ref.step(rc, batch)
        zl, zc = zts.step(zc, batch)
    assert float(zl) == pytest.approx(float(rl), abs=1e-7)
    for a, b in zip(jax.tree.leaves(rc[0]), jax.tree.leaves(zc[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-7)
    # The fp32 master shards hold the replicated master, packed.
    layout = Z.zero_bucket_layout(jax.tree.leaves(params), 2, 1 << 20)
    m_packed = layout.pack(jax.tree.leaves(rc[1].master))
    for packed, z in zip(m_packed, zc[1].master):
        np.testing.assert_allclose(np.asarray(packed), np.asarray(z),
                                   rtol=2e-6, atol=1e-7)
        assert z.dtype == jnp.float32


def test_zero_requires_a_fused_optimizer():
    import optax

    params, loss_fn, _ = _problem()
    with pytest.raises(ValueError, match="fused optimizer"):
        ts = make_split_train_step(loss_fn, optax.adam(1e-3),
                                   zero=Z.ZeroConfig(size=2))
        ts.init(params)


def test_zero_config_resolves_size_from_mesh():
    from horovod_tpu.parallel.mesh import create_mesh

    assert Z.ZeroConfig(size=3).resolved_size() == 3
    mesh = create_mesh()
    assert Z.ZeroConfig(axis="data", mesh=mesh).resolved_size() == \
        mesh.shape["data"]
    with pytest.raises(ValueError):
        Z.ZeroConfig().resolved_size()


# ---- byte predictors -------------------------------------------------

def test_zero_byte_predictors_agree_exactly():
    """The jaxpr-walker predictor and the layout arithmetic must agree
    to the byte — the invariant the zero_sweep/telemetry
    reconciliation stands on."""
    from horovod_tpu.telemetry.predict import (
        eager_zero_bytes,
        zero_layout_bytes,
    )

    params, loss_fn, batch = _problem()
    for size in (2, 4):
        walked = eager_zero_bytes(loss_fn, params, batch, size=size,
                                  bucket_bytes=128)
        layout = Z.zero_bucket_layout(jax.tree.leaves(params), size, 128)
        assert walked == zero_layout_bytes(layout)


def test_optimizer_state_bytes():
    state = {"mu": jnp.zeros((10,), jnp.float32),
             "nu": jnp.zeros((10,), jnp.bfloat16), "n": 3}
    assert Z.optimizer_state_bytes(state) == 40 + 20
