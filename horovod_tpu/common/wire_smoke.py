"""Multi-channel striped wire smoke (``make wire-smoke``).

Proves the striped transport end to end on loopback, no jax needed:

1. Selftest rcs: the uncompressed ring at K in {1, 4} is BIT-IDENTICAL
   to the ring-order reference (incl. the N=2 shared-socket case and
   CRC framing), and the SIMD kernels match scalar bit-for-bit.
2. Byte reconciliation on a REAL 2-rank job at K=4: the per-channel
   tx/rx counters sum exactly to the wire totals, every established
   channel moved bytes (a dead stripe must show as imbalance, and a
   healthy run must have none), and uncompressed wire == logical.
3. K=1 vs K=4 transport bandwidth at 16 MiB: the striped engine's
   wire-time goodput must beat the single-socket baseline by a real
   margin (>= 1.25x here — a smoke bound chosen to stay green under
   CI load; loopback TCP is no fabric, so the ratio is a check of the
   mechanism and never a rate to report).

Exit 0 on success; prints one WIRE_SMOKE json line per check.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run_loopback_ranks(child_src, sentinel, ranks, env_extra,
                        timeout=600):
    """Spawn ``ranks`` local subprocesses wired as ONE Horovod job over
    a fresh loopback port, run ``child_src`` in each, and return rank
    0's ``sentinel``-prefixed JSON payload."""
    from horovod_tpu.runner.util import free_port

    port = free_port("127.0.0.1")
    procs = []
    try:
        for r in range(ranks):
            env = dict(os.environ)
            env.update({
                "HOROVOD_RANK": str(r), "HOROVOD_SIZE": str(ranks),
                "HOROVOD_LOCAL_RANK": str(r),
                "HOROVOD_LOCAL_SIZE": str(ranks),
                "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
                "HOROVOD_CONTROLLER_PORT": str(port),
                "HVDTPU_REPO": REPO,
            })
            env.update(env_extra)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", child_src],
                stdout=subprocess.PIPE if r == 0 else subprocess.DEVNULL,
                stderr=subprocess.DEVNULL, text=True, env=env))
        out, _ = procs[0].communicate(timeout=timeout)
        for p in procs[1:]:
            p.wait(timeout=60)
        payload = None
        for line in out.splitlines():
            if line.startswith(sentinel + " "):
                payload = json.loads(line.split(" ", 1)[1])
        if payload is None:
            raise RuntimeError(f"rank 0 emitted no {sentinel}")
        return payload
    except Exception:
        for p in procs:
            p.kill()
        raise


def _selftest_checks():
    from horovod_tpu.common import basics

    b = basics.HorovodBasics()
    rc = b.simd_selftest()
    assert rc == 0, f"simd_selftest rc={rc}"
    for channels in (1, 4):
        for ranks in (2, 4):
            for count in (1025, 300001):
                rc, err = b.ring_selftest(ranks, count, chunk_bytes=65536,
                                          channels=channels)
                assert rc == 0 and err == 0.0, (channels, ranks, count,
                                                rc, err)
    saved = b.wire_crc()
    b.set_wire_crc(True)
    try:
        rc, err = b.ring_selftest(2, 5000, chunk_bytes=1024, channels=4)
        assert rc == 0 and err == 0.0, ("crc", rc, err)
    finally:
        b.set_wire_crc(saved)
    print("WIRE_SMOKE " + json.dumps({"check": "selftests", "ok": True}),
          flush=True)


_RECON_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
from horovod_tpu.common import basics, eager_ops
b = basics.HorovodBasics()
b.init()
rank, size = b.rank(), b.size()
x = np.full((1 << 22,), float(rank + 1), np.float32)  # 16 MiB
for i in range(4):
    eager_ops.allreduce_async(x, f"recon.{i}").synchronize()
snap = b.metrics_snapshot()
wire = snap["wire"]
chans = wire["channels"]
est = b.wire_channels_established()
out = {
    "established": est,
    "channels": len(chans),
    "tx": wire["tx_bytes"],
    "rx": wire["rx_bytes"],
    "tx_logical": wire["tx_logical_bytes"],
    "chan_tx_sum": sum(c["tx_bytes"] for c in chans),
    "chan_rx_sum": sum(c["rx_bytes"] for c in chans),
    # At N=2 the paired plan runs each socket one-way (tx on one
    # parity, rx on the other), so the liveness floor is per-channel
    # TRAFFIC (tx+rx), not per-direction.
    "chan_min_traffic": min(c["tx_bytes"] + c["rx_bytes"] for c in chans),
}
b.shutdown()
if rank == 0:
    print("RECON " + json.dumps(out), flush=True)
"""


def _reconciliation_check():
    out = _run_loopback_ranks(
        _RECON_CHILD, "RECON", 2,
        {"HOROVOD_WIRE_CHANNELS": "4", "HOROVOD_WIRE_COMPRESSION": "0",
         "HOROVOD_RING_CHUNK_BYTES": str(1024 * 1024)})
    assert out["established"] == 4, out
    # Exact per-channel reconciliation: stripes sum to the totals, and
    # on a healthy K=4 run every channel carried traffic.
    assert out["chan_tx_sum"] == out["tx"], out
    assert out["chan_rx_sum"] == out["rx"], out
    assert out["tx"] == out["tx_logical"], out  # uncompressed: wire==logical
    assert out["channels"] == 4 and out["chan_min_traffic"] > 0, out
    print("WIRE_SMOKE " + json.dumps(
        {"check": "byte_reconciliation", "ok": True, **out}), flush=True)


# 16 MiB allreduces, 3 to warm up (the first ops after connect pay TCP
# ramp and page faults), 6 timed. busbw_gbps is the NCCL-tests bus
# formula over the whole API path; wire_gbps the same bytes over the
# time the transport itself spent (the core's wire_us histogram), which
# is what the multi-channel engine moves: on loopback the fixed per-op
# API overhead otherwise dilutes the transport's share.
_BUSBW_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, os.environ["HVDTPU_REPO"])
from horovod_tpu.common import basics, eager_ops
b = basics.HorovodBasics()
b.init()
rank, size = b.rank(), b.size()
nbytes, iters = 1 << 24, 6
x = np.full(nbytes // 4, float(rank + 1), np.float32)
try:
    for w in range(3):
        eager_ops.allreduce_async(x, f"bw.w{w}").synchronize()
    wire0 = b.metrics_snapshot()["wire_us"]["sum_us"]
    t0 = time.perf_counter()
    for i in range(iters):
        eager_ops.allreduce_async(x, f"bw.{i}").synchronize()
    dt = (time.perf_counter() - t0) / iters
    wire_dt = (b.metrics_snapshot()["wire_us"]["sum_us"]
               - wire0) / iters / 1e6
finally:
    b.shutdown()
bus = 2 * (size - 1) / size * nbytes
if rank == 0:
    print("BUSBW " + json.dumps(
        {"busbw_gbps": round(bus / dt / 1e9, 4),
         "wire_gbps": round(bus / wire_dt / 1e9, 4)}), flush=True)
"""


def _busbw_check():
    results = {}
    for name, knobs in (
        ("k1", {"HOROVOD_RING_CHUNK_BYTES": str(256 * 1024),
                "HOROVOD_WIRE_CHANNELS": "1"}),
        ("k4", {"HOROVOD_RING_CHUNK_BYTES": str(1024 * 1024),
                "HOROVOD_WIRE_CHANNELS": "4"}),
    ):
        results[name] = _run_loopback_ranks(
            _BUSBW_CHILD, "BUSBW", 2,
            dict(knobs, HOROVOD_WIRE_COMPRESSION="0"))
    ratio = results["k4"]["wire_gbps"] / results["k1"]["wire_gbps"]
    print("WIRE_SMOKE " + json.dumps(
        {"check": "busbw", "k1_wire_gbps": results["k1"]["wire_gbps"],
         "k4_wire_gbps": results["k4"]["wire_gbps"],
         "k1_busbw_gbps": results["k1"]["busbw_gbps"],
         "k4_busbw_gbps": results["k4"]["busbw_gbps"],
         "wire_ratio_k4_over_k1": round(ratio, 3)}), flush=True)
    assert ratio >= 1.25, (
        f"striped wire goodput only {ratio:.2f}x the K=1 baseline "
        f"({results})")


def main():
    sys.path.insert(0, REPO)
    _selftest_checks()
    _reconciliation_check()
    _busbw_check()
    print("WIRE_SMOKE " + json.dumps({"check": "all", "ok": True}),
          flush=True)


if __name__ == "__main__":
    main()
