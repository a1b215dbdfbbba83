"""The yardstick's constants and arithmetic: device peaks and the
operations a training step REQUIRES, computed from shapes.

Kept with the benchmark so that no later PR can move them. Peaks are the
published ones (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
819 GB/s HBM, 16 GB), keyed by ``device_kind`` as jax reports it. A kind
that is not in the table is an error, never a default.
"""

PEAKS = {
    # device_kind: published peaks of ONE chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind, what="bf16_flops"):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}: add it to chipbench/peaks.py "
                       f"with its source (known: {sorted(PEAKS)})")
    return PEAKS[device_kind][what]


def lm_matmul_params(d_model, d_ff, n_heads, n_kv_heads, head_dim,
                     n_layers, vocab):
    """Parameters that multiply a token in a dense llama-family decoder:
    every matrix but the input embedding table (a gather, not a matmul);
    norm gains are elementwise and not counted."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    mlp = 3 * d_model * d_ff
    return n_layers * (attn + mlp) + d_model * vocab


def lm_train_flops_per_token(d_model, d_ff, n_heads, n_kv_heads, head_dim,
                             n_layers, vocab, seq):
    """FLOPs one token of a training step requires: 6 per matmul
    parameter (forward 2, backward 4) plus causal attention, which is
    half of the full 12*L*T*d (scores and values, forward and backward,
    over the lower triangle only). Recomputed work is not credited."""
    n = lm_matmul_params(d_model, d_ff, n_heads, n_kv_heads, head_dim,
                         n_layers, vocab)
    return 6 * n + 6 * n_layers * seq * n_heads * head_dim


def causal_attention_flops(batch, seq, n_heads, head_dim, backward):
    """Required FLOPs of causal self-attention at one layer: QK^T and PV
    are 2*T*T*d each per head over the full square, half under the
    mask; the backward pass needs twice the forward's matmuls again
    (dq, dk, dv, dp: 2.5x in flash form, counted as the required 2x)."""
    fwd = 4 * batch * n_heads * seq * seq * head_dim / 2
    return fwd * (3 if backward else 1)


# ResNet-50 at 224x224: 4.09e9 multiply-adds forward (He et al. 2015,
# table 1, "3.8e9 FLOPs" counts multiply-adds without the 1000-way head
# and shortcuts; torchvision's count with them is 4.09 GMACs). Training
# requires forward + twice that backward: 3 x 2 x 4.09e9 per image.
RESNET50_TRAIN_FLOPS_PER_IMAGE_224 = 3 * 2 * 4.09e9


def mfu(units_per_s, flops_per_unit, device_kind, chips):
    return units_per_s * flops_per_unit / (chips * peak(device_kind))
