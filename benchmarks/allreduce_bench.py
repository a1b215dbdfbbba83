"""Ring-allreduce bus-bandwidth micro-benchmark (BASELINE.json's
north-star transport metric).

Run under the launcher, one process per rank:

    horovodrun -np 4 python benchmarks/allreduce_bench.py \
        --size-mb 64 --iters 10

Every rank allreduces a float32 buffer; rank 0 prints one JSON line with
the achieved algorithm bandwidth (payload/time) and bus bandwidth
(the ring moves 2(N-1)/N x payload per rank, the standard NCCL-tests
convention), for both the first (cold negotiation) and steady-state
(response-cache bitvector) iterations.

On a TPU pod with the xla_ici device plane enabled the same script
measures HBM-to-HBM collectives over ICI; on CPU hosts it measures the
native host TCP ring.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--grouped", type=int, default=0,
                    help="split the payload into N tensors fused by the "
                         "runtime (exercises the fusion buffer)")
    ap.add_argument("--op", default="allreduce",
                    choices=("allreduce", "adasum", "allgather",
                             "reducescatter"),
                    help="collective to time; adasum = allreduce with "
                         "op=Adasum (device-plane recursive doubling); "
                         "allgather/reducescatter require --grouped")
    args = ap.parse_args()
    if args.op in ("allgather", "reducescatter") and not args.grouped:
        ap.error(f"--op {args.op} requires --grouped (the grouped "
                 f"variants are the benched surface)")

    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import xla_ici
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    n = hvd.size()
    elems = int(args.size_mb * (1 << 20) / 4)
    payload_bytes = elems * 4

    # Allocate ONCE, outside the timed region (NCCL-tests convention).
    # The xla_ici device plane only engages for jax.Array inputs, so on
    # TPU the payload must be a device array (HBM-to-HBM over ICI);
    # numpy would silently fall back to the host ring. On the host ring
    # numpy is the honest choice — jax arrays would just add two copies
    # per iteration.
    device_plane = xla_ici.active()

    def make(arr):
        if device_plane:
            import jax.numpy as jnp

            return jnp.asarray(arr)
        return arr

    base = np.full(elems, float(hvd.rank() + 1), np.float32)
    if args.grouped:
        parts = [make(p) for p in np.array_split(base, args.grouped)]
    else:
        payload = make(base)

    def materialize(out):
        # Completion probe must match the plane: on the device plane the
        # result lives in HBM and np.asarray would time a full
        # device→host transfer (dwarfing the collective);
        # block_until_ready is the honest fence there. The host ring's
        # result is already host memory.
        if device_plane:
            import jax

            jax.block_until_ready(out)
        else:
            np.asarray(out)

    names = [f"bench.g{j}" for j in range(args.grouped or 0)]

    def one_iter(i):
        t0 = time.perf_counter()
        if args.op == "allgather":
            outs = hvd.grouped_allgather(parts, names=names)
            materialize(outs[0])
        elif args.op == "reducescatter":
            outs = hvd.grouped_reducescatter(parts, names=names,
                                             op=hvd.Sum)
            materialize(outs[0])
        elif args.grouped:
            op = hvd.Adasum if args.op == "adasum" else hvd.Sum
            outs = hvd.grouped_allreduce(parts, names=names, op=op)
            materialize(outs[0])
        else:
            op = hvd.Adasum if args.op == "adasum" else hvd.Sum
            out = hvd.allreduce(payload, name="bench.allreduce", op=op)
            materialize(out)
        return time.perf_counter() - t0

    cold = one_iter(0)
    times = [one_iter(i + 1) for i in range(args.iters)]
    steady = float(np.median(times))

    if hvd.rank() == 0:
        # NCCL-tests bus-bandwidth conventions per collective: the ring
        # moves 2(N-1)/N x payload per rank for allreduce-likes and
        # (N-1)/N for allgather/reducescatter.
        if args.op in ("allgather", "reducescatter"):
            bus_factor = (n - 1) / n
        else:
            bus_factor = 2.0 * (n - 1) / n
        print(json.dumps({
            "metric": f"ring_{args.op}_bandwidth",
            "op": args.op,
            "plane": "xla_ici" if device_plane else "host_ring",
            "ranks": n,
            "payload_mb": round(payload_bytes / (1 << 20), 2),
            "grouped": args.grouped,
            "cold_s": round(cold, 4),
            "steady_s": round(steady, 4),
            "algo_gbps": round(payload_bytes / steady / 1e9, 3),
            "bus_gbps": round(payload_bytes * bus_factor / steady / 1e9, 3),
        }), flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    sys.exit(main())
