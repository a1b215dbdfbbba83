"""What a training step of Trinity-Mini's share on one chip REQUIRES,
computed from shapes and from the rows the program's own router hands
the experts held here: beside ``peaks.py`` and ``moe_counts.py`` (neither
edited) and kept with the benchmark for the same reason. Recomputed work
is never credited.

The share: a chip holds ``held`` of the ``n_experts`` experts of each
expert layer. Of a step's tokens x K routed slots only those that chose
a held expert are rows of its grouped GEMMs; an even router hands it
tokens x K x held / n_experts of them.
"""

from chipbench import peaks


def visible_pairs(seq, window=0):
    """(query, key) pairs a causal layer scores in one sequence: the
    lower triangle, or with a window W the band ``i - W < j <= i``:
    ``T*W - W*(W-1)/2`` (W >= T: the triangle)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def attention_flops(batch, seq, n_heads, head_dim, window=0):
    """Required FLOPs of one attention layer's score and value products
    in a training step: ``QK^T`` and ``PV`` are 2 FLOPs a pair and
    element of a head each, forward; the backward needs twice the
    forward again (``peaks.causal_attention_flops``' convention: five
    matmuls in flash form, counted as the required four)."""
    return 3 * 4 * batch * n_heads * head_dim * visible_pairs(seq, window)


def attention_bytes(batch, seq, n_heads, n_kv_heads, head_dim, itemsize=2):
    """Bytes one attention layer's flash calls must move if every
    operand is read and every result written once: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    q = batch * seq * n_heads * head_dim
    kv = batch * seq * n_kv_heads * head_dim
    return (2 * q + 2 * kv + 4 * q + 4 * kv) * itemsize


def matmul_params_per_token(d_model, dense_width, expert_width, n_heads,
                            n_kv_heads, head_dim, n_dense, n_expert_layers,
                            vocab, n_experts, n_shared, routed_per_token):
    """Parameters that multiply ONE token on this chip: attention's four
    projections and its output gate in every layer; the dense layers'
    FFN; in an expert layer the router (every token is scored against
    ALL ``n_experts``), the shared expert, and ``routed_per_token`` held
    experts (rows held / tokens: 1 at even routing with an eighth of 128
    experts held and 8 a token); the head over the vocabulary rows held.
    Not the embedding (a gather), not the norm gains."""
    attn = d_model * head_dim * (3 * n_heads + 2 * n_kv_heads)
    expert = 3 * d_model * expert_width
    return ((n_dense + n_expert_layers) * attn
            + n_dense * 3 * d_model * dense_width
            + n_expert_layers * (d_model * n_experts + n_shared * expert
                                 + routed_per_token * expert)
            + d_model * vocab)


def train_flops_per_token(matmul_params, seq, n_heads, head_dim, windows):
    """6 FLOPs a parameter that multiplies the token (forward 2,
    backward 4) plus each layer's attention over ITS visible pairs
    (``windows``: one window a layer, 0 = full)."""
    attn = sum(attention_flops(1, seq, n_heads, head_dim, w)
               for w in windows) / seq
    return 6 * matmul_params + attn


def grouped_gemm_flops(rows_held, d_model, expert_width):
    """Required FLOPs of the grouped GEMMs of a step whose expert layers
    hold ``rows_held`` rows (one number a layer): three matmuls a layer
    in three directions, 2*M*K*N apiece, M the rows HELD."""
    return sum(9 * 2 * rows * d_model * expert_width for rows in rows_held)


def grouped_gemm_bytes(rows_held, d_model, expert_width, held_experts,
                       itemsize=2):
    """Bytes of those nine calls a layer, every operand read and every
    result written once (``moe_counts.grouped_gemm_bytes_per_step``'s
    count with the rows and the experts HELD)."""
    return sum(9 * (rows * d_model + rows * expert_width
                    + held_experts * d_model * expert_width) * itemsize
               for rows in rows_held)


def floor_s(device_kind, flops, nbytes):
    """The least time the chip could take: the larger of FLOPs over the
    published bf16 peak and bytes over the published HBM bandwidth."""
    return max(flops / peaks.peak(device_kind),
               nbytes / peaks.peak(device_kind, "hbm_bytes_per_s"))
