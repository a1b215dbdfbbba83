"""Distributed correctness of the horovod_tpu.torch frontend.

Reference analog: test/parallel/test_torch.py — ops, in-place semantics,
DistributedOptimizer end-to-end training equivalence, SyncBatchNorm vs
single-process big-batch closed form (SURVEY.md §4).
"""

import numpy as np
import pytest

from tests import utils_mp
from tests.utils_mp import run_ranks


@pytest.fixture(scope="module")
def torch_world():
    """``torch_world(size)``: this module's ranks, up since the first
    test that asked for that size: they have imported torch and run
    ``hvd.init()`` ONCE, and each test hands them its own body
    (tests/utils_mp.py: ``World``). A body calls neither ``init`` nor
    ``shutdown``."""
    with utils_mp.worlds("horovod_tpu.torch") as world:
        yield world


def _worker_ops(rank, size):
    import torch
    import horovod_tpu.torch as hvd

    assert hvd.rank() == rank and hvd.size() == size

    # in-place allreduce_ writes into the same storage
    t = torch.full((4, 3), float(rank))
    out = hvd.allreduce_(t, op=hvd.Sum)
    assert out.data_ptr() == t.data_ptr()
    assert torch.allclose(t, torch.full((4, 3),
                                        float(sum(range(size)))))

    # out-of-place leaves input untouched
    t2 = torch.full((5,), float(rank))
    r = hvd.allreduce(t2)  # Average
    assert torch.allclose(t2, torch.full((5,), float(rank)))
    assert torch.allclose(r, torch.full((5,),
                                        sum(range(size)) / size))

    # bfloat16
    bf = hvd.allreduce(torch.full((8,), float(rank),
                                  dtype=torch.bfloat16), op=hvd.Sum)
    assert bf.dtype == torch.bfloat16
    assert torch.allclose(bf.float(),
                          torch.full((8,), float(sum(range(size)))))

    # allgather unequal first dim
    g = hvd.allgather(torch.full((rank + 1, 2), float(rank)))
    assert g.shape == (sum(range(1, size + 1)), 2)

    # broadcast_ in place from root
    b = torch.full((3,), float(rank))
    hvd.broadcast_(b, root_rank=size - 1)
    assert torch.allclose(b, torch.full((3,), float(size - 1)))

    # alltoall / reducescatter
    a2a = hvd.alltoall(torch.arange(size * 2, dtype=torch.float32)
                       + 100.0 * rank, splits=[2] * size)
    exp = np.concatenate(
        [np.arange(rk_ * 0 + rank * 2, rank * 2 + 2,
                   dtype=np.float32) + 100 * rk_
         for rk_ in range(size)])
    np.testing.assert_allclose(a2a.numpy(), exp)

    rs = hvd.reducescatter(torch.full((size * 2, 3), float(rank + 1)),
                           op=hvd.Sum)
    assert torch.allclose(rs, torch.full((2, 3),
                                         float(sum(range(1, size + 1)))))

    # grouped allgather / reducescatter (atomic negotiation)
    gouts = hvd.grouped_allgather(
        [torch.full((rank + 1, 2), float(rank + i)) for i in range(3)])
    for i, g in enumerate(gouts):
        exp = np.concatenate(
            [np.full((rk + 1, 2), float(rk + i)) for rk in range(size)])
        np.testing.assert_allclose(g.numpy(), exp)
    routs = hvd.grouped_reducescatter(
        [torch.full((size * 2, 3), float(rank + 1 + i))
         for i in range(2)], op=hvd.Sum)
    for i, r_ in enumerate(routs):
        assert torch.allclose(
            r_, torch.full((2, 3),
                           float(sum(rk + 1 + i for rk in range(size)))))

    # broadcast_object / allgather_object
    obj = hvd.broadcast_object({"x": rank}, root_rank=0)
    assert obj == {"x": 0}
    objs = hvd.allgather_object(rank * 10)
    assert objs == [rk * 10 for rk in range(size)]

    hvd.barrier()
    return "ok"


@pytest.mark.parametrize("size", [2, 4])
def test_torch_ops(torch_world, size):
    assert torch_world(size).run(_worker_ops) == ["ok"] * size


def _worker_device_bridge(rank, size):
    """Device-tensor path (ref adapter_v2.cc/ready_event.cc): tensors
    bridge via dlpack into the jax frontend's data plane instead of the
    CPU numpy view. HOROVOD_TORCH_DEVICE_OPS=1 forces the bridge so the
    path is exercised with jax CPU arrays (identical code path to TPU)."""
    import torch
    import horovod_tpu.torch as hvd
    from horovod_tpu.torch import mpi_ops

    hvd.init()
    try:
        t = torch.full((4, 3), float(rank))
        assert mpi_ops._use_device_bridge(t)  # env forces it

        # in-place: result lands in the original tensor object
        out = hvd.allreduce_(t, op=hvd.Sum)
        assert out is t
        assert torch.allclose(t, torch.full((4, 3),
                                            float(sum(range(size)))))

        # out-of-place average
        r = hvd.allreduce(torch.full((5,), float(rank)))
        assert torch.allclose(r, torch.full((5,),
                                            sum(range(size)) / size))

        # bfloat16 survives the dlpack round trip
        bf = hvd.allreduce(torch.full((8,), float(rank),
                                      dtype=torch.bfloat16), op=hvd.Sum)
        assert bf.dtype == torch.bfloat16
        assert torch.allclose(bf.float(),
                              torch.full((8,), float(sum(range(size)))))

        # broadcast_ in-place from a non-zero root
        b = torch.full((3,), float(rank))
        hvd.broadcast_(b, root_rank=size - 1)
        assert torch.allclose(b, torch.full((3,), float(size - 1)))

        # allgather with unequal first dims
        g = hvd.allgather(torch.full((rank + 1, 2), float(rank)))
        assert g.shape == (sum(range(1, size + 1)), 2)

        # reducescatter
        rs = hvd.reducescatter(torch.full((size * 2, 3), float(rank + 1)),
                               op=hvd.Sum)
        assert torch.allclose(rs, torch.full((2, 3),
                                             float(sum(range(1, size + 1)))))

        # grouped: one atomic negotiation through the bridge, results
        # land in-place in the original tensors
        ts = [torch.full((3,), float(rank + i)) for i in range(3)]
        outs = hvd.grouped_allreduce_(ts, op=hvd.Sum,
                                      names=[f"bg.{i}" for i in range(3)])
        for i, (t, o) in enumerate(zip(ts, outs)):
            assert o is t
            assert torch.allclose(t, torch.full(
                (3,), float(sum(rk + i for rk in range(size)))))
        return "ok"
    finally:
        hvd.shutdown()


def test_torch_device_bridge():
    # a world of its own: the environment is read at ``init``
    assert run_ranks(_worker_device_bridge, 2,
                     env={"HOROVOD_TORCH_DEVICE_OPS": "1"},
                     timeout=180) == ["ok"] * 2


def _make_model(seed):
    import torch

    torch.manual_seed(seed)
    return torch.nn.Sequential(
        torch.nn.Linear(10, 16), torch.nn.ReLU(), torch.nn.Linear(16, 4))


def _worker_optimizer(rank, size):
    import torch
    import horovod_tpu.torch as hvd

    # Distributed run: each rank sees a different shard of the batch.
    torch.manual_seed(1234)
    full_x = torch.randn(8 * size, 10)
    full_y = torch.randn(8 * size, 4)
    x = full_x[rank * 8:(rank + 1) * 8]
    y = full_y[rank * 8:(rank + 1) * 8]

    model = _make_model(seed=7 + rank)  # deliberately diverged init
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    opt = hvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    hvd.broadcast_optimizer_state(opt, root_rank=0)

    for _ in range(3):
        opt.zero_grad()
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        opt.step()

    # Single-process reference on the full batch (grad averaging over
    # ranks == full-batch gradient since shards are equal-sized).
    ref = _make_model(seed=7)
    ref_opt = torch.optim.SGD(ref.parameters(), lr=0.1)
    for _ in range(3):
        ref_opt.zero_grad()
        torch.nn.functional.mse_loss(ref(full_x), full_y).backward()
        ref_opt.step()

    for (n, p), (_, rp) in zip(model.named_parameters(),
                               ref.named_parameters()):
        np.testing.assert_allclose(p.detach().numpy(),
                                   rp.detach().numpy(), rtol=1e-4,
                                   atol=1e-5), n
    return "ok"


def test_distributed_optimizer_matches_full_batch(torch_world):
    assert torch_world(2).run(_worker_optimizer) == ["ok"] * 2


def _worker_optimizer_fp16(rank, size):
    import torch
    import horovod_tpu.torch as hvd

    model = _make_model(seed=3 + rank)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters(),
        compression=hvd.Compression.fp16)
    torch.manual_seed(99)
    x, y = torch.randn(4, 10), torch.randn(4, 4)
    opt.zero_grad()
    torch.nn.functional.mse_loss(model(x), y).backward()
    opt.step()
    # all ranks identical after step (same data, averaged grads)
    blob = hvd.allgather_object(
        [p.detach().numpy() for p in model.parameters()])
    for other in blob[1:]:
        for a, b in zip(blob[0], other):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    return "ok"


def test_distributed_optimizer_fp16(torch_world):
    assert torch_world(2).run(_worker_optimizer_fp16) == ["ok"] * 2


def _worker_backward_passes(rank, size):
    import torch
    import horovod_tpu.torch as hvd

    model = torch.nn.Linear(4, 1, bias=False)
    with torch.no_grad():
        model.weight.fill_(0.0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1.0),
        named_parameters=model.named_parameters(),
        backward_passes_per_step=2)
    # two backward passes, one step
    for i in range(2):
        out = model(torch.full((1, 4), float(rank + 1 + i))).sum()
        out.backward()
    opt.step()
    # grad wrt w = x; accumulated over 2 passes, averaged by /2 then
    # across ranks: mean over ranks of mean(x_i)
    exp = -np.mean([np.mean([rk + 1, rk + 2]) for rk in range(size)])
    np.testing.assert_allclose(
        model.weight.detach().numpy(), np.full((1, 4), exp), rtol=1e-5)
    return "ok"


def test_backward_passes_per_step(torch_world):
    assert torch_world(2).run(_worker_backward_passes) == ["ok"] * 2


def _worker_sync_bn(rank, size):
    import torch
    import horovod_tpu.torch as hvd

    torch.manual_seed(0)
    full = torch.randn(4 * size, 3, 5, 5)
    x = full[rank * 4:(rank + 1) * 4].clone().requires_grad_(True)

    bn = hvd.SyncBatchNorm(3)
    out = bn(x)
    loss = (out * out).mean()
    loss.backward()

    # reference: plain BatchNorm over the concatenated global batch
    xr = full.clone().requires_grad_(True)
    bn_ref = torch.nn.BatchNorm2d(3)
    out_ref = bn_ref(xr)
    ((out_ref * out_ref).mean() / size * size).backward()

    np.testing.assert_allclose(
        out.detach().numpy(),
        out_ref.detach().numpy()[rank * 4:(rank + 1) * 4],
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(bn.running_mean.numpy(),
                               bn_ref.running_mean.numpy(), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(bn.running_var.numpy(),
                               bn_ref.running_var.numpy(), rtol=1e-3,
                               atol=1e-4)
    # grad wrt local shard matches the global-batch gradient. The ref
    # loss averages over the full batch (N*size elements) while ours
    # averages over N, so scale ref grads by size.
    np.testing.assert_allclose(
        x.grad.numpy(),
        xr.grad.numpy()[rank * 4:(rank + 1) * 4] * size,
        rtol=1e-3, atol=1e-5)
    return "ok"


def test_sync_batch_norm(torch_world):
    assert torch_world(2).run(_worker_sync_bn) == ["ok"] * 2


def _worker_lightning_protocol(rank, size):
    import numpy as np
    import torch

    import horovod_tpu.torch as hvd
    from horovod_tpu.spark.lightning import train_protocol_model

    torch.manual_seed(1234 + rank)  # diverge per rank pre-broadcast

    class Lit(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.net = torch.nn.Linear(3, 1)

        def forward(self, x):
            return self.net(x)

        def training_step(self, batch, batch_idx):
            x, y = batch
            return torch.nn.functional.mse_loss(self(x), y)

        def configure_optimizers(self):
            return torch.optim.SGD(self.parameters(), lr=0.05)

    model = Lit()
    rng = np.random.RandomState(rank)  # rank-local data shard
    x = torch.from_numpy(rng.randn(16, 3).astype("float32"))
    y = x @ torch.tensor([[1.0], [-1.0], [2.0]])
    train_protocol_model(model, x, y, batch_size=8, epochs=2,
                         distributed=True)
    # broadcast + averaged grads => identical params on all ranks
    digest = float(sum(p.detach().sum() for p in model.parameters()))
    digests = hvd.allgather_object(digest)
    assert all(abs(d - digests[0]) < 1e-6 for d in digests), digests
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_lightning_protocol_distributed(torch_world, size):
    assert torch_world(size).run(_worker_lightning_protocol, timeout=180) \
        == ["ok"] * size
