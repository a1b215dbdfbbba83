"""What a training step of a looped decoder (Ouro's LoopLM: ONE stack of
layers run ``trips`` times with shared weights, an exit after every
trip) REQUIRES, computed from shapes: beside ``peaks.py`` and
``afmoe_counts.py`` (neither edited) and kept with the benchmark for the
same reason. A weight counts once a USE: a layer's matrices multiply a
token ``trips`` times a step, the head's once an exit. Recomputed work
is never credited.
"""

from chipbench import afmoe_counts, peaks


def matmul_param_uses_per_token(d_model, d_ff, n_heads, n_kv_heads,
                                head_dim, layers, vocab, trips):
    """Parameters that multiply ONE token in one step, a use each: every
    trip the four attention projections and the SwiGLU's three matrices
    of every layer, and at its exit the head over the vocabulary rows
    held and the gate's ``d_model`` weights. Not the lookup (a gather),
    not the norm gains (elementwise)."""
    layer = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads) \
        + 3 * d_model * d_ff
    return trips * (layers * layer + vocab * d_model + d_model)


def train_flops_per_token(d_model, d_ff, n_heads, n_kv_heads, head_dim,
                          layers, vocab, trips, seq):
    """6 FLOPs a parameter-use (forward 2, backward 4) plus causal
    attention over the lower triangle at every layer VISIT
    (``afmoe_counts.attention_flops``)."""
    uses = matmul_param_uses_per_token(d_model, d_ff, n_heads, n_kv_heads,
                                       head_dim, layers, vocab, trips)
    attn = trips * layers * afmoe_counts.attention_flops(
        1, seq, n_heads, head_dim) / seq
    return 6 * uses + attn


def exit_heads_flops(tokens, trips, d_model, vocab):
    """Required FLOPs of the exits' heads in one step: the logits of
    every token at every exit and the two gradients of that product,
    ``2 x tokens x d_model x vocab`` apiece. The cross-entropies, the
    gates, the exit distribution and the entropy are elementwise or a
    reduction a token: not counted, so the share reads low by them."""
    return 3 * 2 * trips * tokens * d_model * vocab


def floor_s(device_kind, flops):
    """The least time the chip could take: the FLOPs over the published
    bf16 peak (a [tokens, d_model] x [d_model, vocab] product at 2048 x
    12,288 is far on the compute side of the roofline)."""
    return flops / peaks.peak(device_kind)

