"""Single-chip long-context training benchmark (flash-kernel path).

Proves the net-new long-context stack's single-chip leg (SURVEY.md
§5.7): the streamed pallas flash kernels (VMEM O(block), independent of
sequence length — see ops/flash_attention.py) train the 1.39B flagship
at sequence lengths the round-3 kernels could not compile (scoped-VMEM
OOM in the backward at T=8192). The multi-chip leg (ring / Ulysses
sequence parallelism) reuses the same kernels via
``flash_attention_chunk``; this benchmark is the in-chip baseline those
paths are compared against.

Run on a real TPU chip::

    python benchmarks/long_context_bench.py [--out results.json]

Writes one row per (batch, seq) config: MFU, tokens/s, ms/step. The
orchestrating parent spawns one chip child per row and never touches a
jax backend itself (a chip belongs to one process at a time); a child
that finds no TPU fails, and any failed row fails the run.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# (batch, seq, remat): 8192+ tokens of context on ONE chip; t16384 at
# b1 is the largest activation footprint that fits beside the 1.4B
# model. The remat tradeoff flips with T: the flagship's "attn+gate"
# (save FFN gate residuals, skip their recompute) wins at t2048 but
# its per-layer [B,T,d_ff] saves grow linearly in T and OOM HBM at
# t8192 — the t8192 row drops to "attn", and at t16384 the r5 flagship
# geometry (d_ff 13312) needs full remat even for the flash residuals'
# neighbors to fit.
CONFIGS = [(1, 16384, True), (2, 8192, "attn"), (4, 2048, None)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write JSON rows here")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--one", type=int, default=0,
                    help="child mode: run ONLY config #N (1-based)")
    args = ap.parse_args()

    if args.one:
        # Child mode: ONE config in its own process. The fused step
        # (not the split grad/apply) — at these activation footprints
        # the split layout's non-donatable gradient copy is what OOMs.
        import bench  # repo-root bench machinery (MFU accounting)

        bench.require_tpu("long_context_bench")
        bench.enable_compile_cache()
        batch, seq, remat = CONFIGS[args.one - 1]
        cfg = bench._flagship_cfg()
        if remat is not None:
            cfg = dataclasses.replace(cfg, remat=remat)
        row = bench.run_spmd_fused(cfg, batch, seq, args.steps,
                                   f"long_context_mfu_t{seq}",
                                   f"pure-bf16 seq {seq}")
        print(json.dumps(row), flush=True)
        return

    # Orchestrator: one child per config, so a failing row cannot take
    # the others down. This parent imports no jax: each child needs the
    # chip to itself.
    rows = []
    for i in range(1, len(CONFIGS) + 1):
        batch, seq, remat = CONFIGS[i - 1]
        t0 = time.time()
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 str(i), "--steps", str(args.steps)],
                stdout=subprocess.PIPE, text=True, timeout=540,
                check=True)  # stderr passes through: a failure shows
            row = None
            for line in reversed(out.stdout.strip().splitlines()):
                try:
                    row = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
            if row is None:
                raise RuntimeError(
                    f"no row in child output: {out.stdout[-200:]!r}")
        except Exception as e:  # noqa: BLE001 — keep the other rows
            row = {"metric": f"long_context_mfu_t{seq}",
                   "error": f"{type(e).__name__}: {str(e)[:200]}"}
        row["wall_s"] = round(time.time() - t0, 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        payload = {
            "note": "1.4B flagship, streamed flash kernels, one real "
                    "chip; one subprocess per row. "
                    "t8192/t16384 rows were scoped-VMEM compile errors "
                    "before the r4 kernel streaming "
                    "(docs/benchmarks.md).",
            "rows": rows,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    if any("error" in row for row in rows):
        sys.exit(2)


if __name__ == "__main__":
    main()
