"""Lane "hvd": the Horovod API, one rank per chip, exactly as
``examples/jax/jax_resnet50.py`` and ``bench.make_eager_step`` use it:
``hvd.init()``, a jitted ``value_and_grad``, ``hvd.allreduce_gradients``
on the ``xla_ici`` device plane, a jitted donated apply. At ``ranks`` 1
it runs in the benchmark's child; at 4 the parent starts the ranks under
``python -m horovod_tpu.runner.launch --tpu-pod``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _plane_bytes(snap):
    """(device-plane payload, host-ring payload + wire) bytes so far."""
    device = sum(v["bytes"] for v in snap["device_ops"].values())
    host = sum(v["bytes"] for v in snap["ops"].values())
    return device, host + snap["wire"]["tx_bytes"] + snap["wire"]["rx_bytes"]


class Lane:
    def __init__(self, traffic):
        self.ranks = traffic["ranks"]

    def start(self):
        """Before the backend is touched: a rank of a multi-process job
        must bring jax.distributed up (inside hvd.init) first."""
        import horovod_tpu.jax as hvd
        from horovod_tpu.jax import xla_ici

        hvd.init()   # on a TPU: brings the device plane up, or raises
        self.hvd = hvd
        self.rank, self.size = hvd.rank(), hvd.size()
        if self.size != self.ranks:
            raise SystemExit(f"lane hvd: the cell asks for {self.ranks} "
                             f"ranks, hvd.size() is {self.size}")
        if not xla_ici.active():
            raise SystemExit("lane hvd: the xla_ici device plane is not "
                             "active")

    def build(self, model, key, jit_kwargs):
        import optax

        from horovod_tpu.jax.optimizer import allreduce_gradients

        hvd = self.hvd
        # COMMITTED to this rank's chip from the start (under a pod
        # launch jax.devices()[0] is rank 0's): the device plane commits
        # the gradients, and a carry that flips from uncommitted to
        # committed after step one is a new jit signature — a recompile
        # inside the window.
        here = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        k_init, k_batch = jax.random.split(key)
        # Same seed, same weights on every rank; each rank its own batch.
        params, state = jax.jit(model.init, out_shardings=here)(k_init)
        batch = jax.jit(model.batch, out_shardings=here)(
            jax.random.fold_in(k_batch, self.rank))
        tx = model.optimizer(self.size)
        opt = jax.jit(tx.init, out_shardings=here)(params)

        grad_fn = jax.jit(
            lambda p, s, d: jax.value_and_grad(model.loss, has_aux=True)(
                p, s, d), **jit_kwargs)

        # Grads are not donated: they arrive as donation-aliased outputs
        # of the device plane's program (bench._eager_parts).
        @functools.partial(jax.jit, donate_argnums=(1, 2), **jit_kwargs)
        def apply_fn(grads, params, opt):
            updates, opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt

        text = grad_fn.lower(params, state, batch).as_text()
        note = jax.profiler.TraceAnnotation

        def step(carry, batch):
            params, state, opt = carry
            with note("grad"):
                (loss, state), grads = grad_fn(params, state, batch)
            with note("allreduce"):
                grads = allreduce_gradients(grads, op=hvd.Average,
                                            donate=True)
            with note("apply"):
                params, opt = apply_fn(grads, params, opt)
            return loss, (params, state, opt)

        return step, (params, state, opt), batch, text

    def params_of(self, carry):
        return carry[0]

    def agree(self, n):
        """Every rank must run the same number of steps: rank 0's."""
        if self.size == 1:
            return n
        got = np.asarray(self.hvd.allgather(
            jnp.asarray([n], jnp.int32), name="chipbench.steps"))
        return int(got[0])

    def counters(self):
        return self.hvd.metrics()

    def check(self, counters, steps, grad_bytes, params):
        """Gradients must have ridden the device plane (host ring 0 B),
        and the ranks must hold the same parameters."""
        before, after = counters
        device, host = (b - a for a, b in zip(_plane_bytes(before),
                                              _plane_bytes(after)))
        faults = []
        if device < grad_bytes * steps or host != 0:
            faults.append(
                f"gradients must ride the device plane: {device} B there "
                f"(need >= {grad_bytes * steps}), {host} B on the host "
                "ring (need 0)")
        if self.size > 1:
            checksum = sum(jnp.sum(x.astype(jnp.float32))
                           for x in jax.tree.leaves(params))
            sums = np.asarray(self.hvd.allgather(
                jnp.reshape(checksum, (1,)), name="chipbench.checksum"))
            if not (sums == sums[0]).all():
                faults.append(f"parameter checksums differ across ranks: "
                              f"{sums.tolist()}")
        return faults

    def close(self):
        self.hvd.shutdown()
