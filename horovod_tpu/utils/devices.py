"""The accelerator a measurement ran on: published peaks, and the check
that there is one.

One table, keyed by substrings of ``device_kind`` (a v5e reports
``"TPU v5 lite"``), shared by ``StepTimer.mfu`` (FLOP/s) and whatever
takes a bandwidth share (HBM bytes/s). A device that is not in the table
is an error, never a default: a utilization against a made-up peak is
not a measurement.

Sources: Google Cloud TPU documentation, per-chip figures — v4 275
TFLOP/s bf16, 1228 GB/s HBM; v5e 197 TFLOP/s, 819 GB/s; v5p 459
TFLOP/s, 2765 GB/s; v6e (Trillium) 918 TFLOP/s, 1640 GB/s.
"""

# A v5p reports the bare "TPU v5"; longest key first keeps "v5 lite" its own.
PEAK_BF16_FLOPS = {"v4": 275e12, "v5e": 197e12, "v5 lite": 197e12,
                   "v5": 459e12, "v5p": 459e12, "v6e": 918e12,
                   "v6 lite": 918e12, "trillium": 918e12}

PEAK_HBM_BYTES_PER_S = {"v4": 1228e9, "v5e": 819e9, "v5 lite": 819e9,
                        "v5": 2765e9, "v5p": 2765e9, "v6e": 1640e9,
                        "v6 lite": 1640e9, "trillium": 1640e9}


def match_device_table(device, table):
    """``table``'s entry for ``device`` (a jax device, or its
    ``device_kind`` string): longest-key-first substring match, so
    ``"v5 lite"`` wins over a shorter key. Raises ``KeyError`` for a
    device kind the table does not know."""
    kind = device if isinstance(device, str) else device.device_kind
    low = kind.lower()
    for key in sorted(table, key=len, reverse=True):
        if key in low:
            return table[key]
    raise KeyError(
        f"device_kind {kind!r} is not in the peak table "
        f"({sorted(table)}): add its published peak with a source "
        "instead of measuring against a default")


def require_tpu(what):
    """Device 0, after checking it is a TPU. Measurement and smoke paths
    call this first: a run that lost the chip must fail, not time the
    CPU under a device metric's name."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"{what} runs on a TPU and found platform {dev.platform!r} "
            f"({dev.device_kind}); it has no CPU fallback")
    return dev
