"""Single-chip autoregressive decode benchmark (KV-cache path).

Measures ``llama_generate`` (models/generate.py: one compiled
prefill+decode program, per-layer KV caches updated in-place via
dynamic_update_slice) on the real chip. Decode is HBM-bandwidth-bound —
every step streams the full parameter set plus the KV cache — so
alongside tokens/s this reports **MBU** (memory-bandwidth utilization:
bytes-that-must-move per step / step time / peak HBM bandwidth), the
decode analog of training MFU.

Per-step time is isolated by differencing two generation lengths
(256 vs 32 new tokens): each timed call re-runs the prefill too, and
at large batch the prefill is a material fraction of the wall time —
dividing a whole call by its decode steps would overstate ms/step.

Run on a real TPU chip::

    python benchmarks/decode_bench.py [--out results.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# (batch, prompt_len): bs1 is the latency point, bs16/bs64 throughput.
CONFIGS = [(1, 128), (16, 128), (64, 128)]
NEW_LONG, NEW_SHORT = 256, 32


def _paged_row(params, cfg, batch=16, t0_len=128, new_tokens=64):
    """Paged-cache decode throughput on the same chip: the serving
    engine's continuous-batching step (host-gathered paged KV,
    models/generate.llama_decode_step) at a fixed batch, all requests
    arriving at t=0. Reports the paged lane's tok/s next to the fused
    contiguous kernel's headline so the host-gather tax — the gap a
    device-resident paged-attention kernel would close (docs/
    serving.md) — is a number, not a guess."""
    import time as _time

    import numpy as np

    from horovod_tpu.serving.engine import DecodeEngine
    from horovod_tpu.serving.scheduler import Request

    eng = DecodeEngine(params, cfg, block_size=32,
                       n_blocks=batch * ((t0_len + new_tokens) // 32 + 2),
                       max_batch=batch, max_context=t0_len + new_tokens)
    rng = np.random.default_rng(1)
    for rid in range(batch):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=t0_len).astype(np.int32),
            max_new_tokens=new_tokens))
    eng.step()  # admit + compile prefill/decode off the clock
    t0 = _time.time()
    steps0, toks0 = eng.steps, eng.tokens_out
    eng.run_until_idle()
    dt = _time.time() - t0
    steps = eng.steps - steps0
    tok_s = (eng.tokens_out - toks0) / dt
    return {
        "metric": f"decode_paged_tok_s_b{batch}",
        "value": round(tok_s, 1),
        "unit": f"tok/s continuous-batching paged KV (batch {batch}, "
                f"prompt {t0_len}, {new_tokens} new, "
                f"{dt / max(steps, 1) * 1e3:.2f} ms/step incl host "
                "gather)",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--paged", action="store_true",
                    help="also run the paged-KV serving-engine lane")
    args = ap.parse_args()

    import numpy as np

    import bench
    from horovod_tpu.models import llama_init
    from horovod_tpu.models.generate import llama_generate
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.utils.devices import PEAK_HBM_BYTES_PER_S

    device = bench.require_tpu("decode_bench")
    enable_compile_cache()

    cfg = bench._flagship_cfg()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    hbm_peak = bench.match_device_table(device, PEAK_HBM_BYTES_PER_S)

    def timed(gen, prompt, reps=3):
        # Materialize to HOST, not block_until_ready: on some PJRT
        # transports block_until_ready returns before the program
        # finishes, which once inflated this row 1000x. The [B, T+new]
        # int32 copy itself is microseconds.
        t0 = time.time()
        np.asarray(gen(params, prompt))
        first_s = time.time() - t0
        t0 = time.time()
        for _ in range(reps):
            np.asarray(gen(params, prompt))
        return first_s, (time.time() - t0) / reps

    rows = []
    for batch, t0_len in CONFIGS:
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, t0_len), 0, cfg.vocab_size)
        gen_long = jax.jit(
            lambda p, t: llama_generate(p, t, cfg, NEW_LONG))
        gen_short = jax.jit(
            lambda p, t: llama_generate(p, t, cfg, NEW_SHORT))
        first_s, dt_long = timed(gen_long, prompt)
        _, dt_short = timed(gen_short, prompt)
        # Decode-only per-step time: the prefill and fixed dispatch
        # costs cancel in the difference.
        step_s = (dt_long - dt_short) / (NEW_LONG - NEW_SHORT)
        tok_s = batch / step_s
        # Bytes per decode step: all params + the KV cache traffic.
        # _decode_attention reads the FULL padded cache
        # [B, t0+new, Hkv, D] every step (dense einsum, masked by
        # index), so a run of n steps streams n*(t0+n) positions; the
        # differenced window's effective length per step is
        # (L*(t0+L) - S*(t0+S)) / (L-S) = t0 + L + S.
        kv_mean = (cfg.n_layers * batch
                   * (t0_len + NEW_LONG + NEW_SHORT)
                   * cfg.n_kv_heads * cfg.head_dim * 2 * 2)
        mbu = (param_bytes + kv_mean) / step_s / hbm_peak
        row = {
            "metric": f"decode_tok_s_b{batch}",
            "value": round(tok_s, 1),
            "unit": f"tok/s decode-only ({n_params / 1e6:.0f}M params "
                    f"bf16, batch {batch}, prompt {t0_len}, "
                    f"{step_s * 1e3:.2f} ms/step, MBU {mbu:.2f}, "
                    f"first call incl compile {first_s:.0f}s, "
                    f"{jax.devices()[0].device_kind})",
            "vs_baseline": round(mbu, 3),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.paged:
        row = _paged_row(params, cfg)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        payload = {
            "note": "Decode (KV cache) on one real chip; per-step time "
                    "isolated by differencing 256- vs 32-token "
                    "generations (prefill cancels). vs_baseline "
                    "carries MBU (step bytes / step time / peak HBM "
                    "bw) - the bandwidth-roofline utilization, "
                    "decode's analog of MFU.",
            "rows": rows,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
