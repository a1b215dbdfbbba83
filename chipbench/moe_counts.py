"""What a training step of a sparse-expert decoder REQUIRES, computed
from shapes: beside ``peaks.py`` (which is not edited) and kept with the
benchmark for the same reason, so that no later PR can move the
yardstick. Recomputed work is never credited: a remat mode that runs a
forward grouped GEMM a second time lengthens the time and leaves these
counts where they are.
"""

from chipbench import peaks


def moe_matmul_params(d_model, expert_width, n_heads, n_kv_heads,
                      head_dim, n_layers, vocab, n_experts,
                      experts_per_token):
    """Parameters that multiply ONE token in a top-K sparse-expert
    llama-family decoder: attention's four projections, the router
    (d_model x experts: every token is scored against all of them), the
    K experts it is routed to (three matrices of d_model x width each),
    and the output head. Not the input embedding table (a gather), not
    the norm gains (elementwise), not the experts a token never sees."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    router = d_model * n_experts
    experts = experts_per_token * 3 * d_model * expert_width
    return n_layers * (attn + router + experts) + d_model * vocab


def moe_train_flops_per_token(d_model, expert_width, n_heads, n_kv_heads,
                              head_dim, n_layers, vocab, n_experts,
                              experts_per_token, seq):
    """FLOPs one token of a training step requires: 6 per parameter that
    multiplies it (forward 2, backward 4) plus causal attention as
    ``peaks.lm_train_flops_per_token`` counts it (6*L*T*d over the lower
    triangle)."""
    n = moe_matmul_params(d_model, expert_width, n_heads, n_kv_heads,
                          head_dim, n_layers, vocab, n_experts,
                          experts_per_token)
    return 6 * n + 6 * n_layers * seq * n_heads * head_dim


def grouped_gemm_flops_per_step(tokens, experts_per_token, d_model,
                                expert_width, n_layers):
    """Required FLOPs of the expert layers' grouped GEMMs in one training
    step: three matmuls a layer (gate, up: [M, d] x [d, w]; down: [M, w]
    x [w, d]; M = tokens x K routed rows, each row through ONE expert),
    each in three directions (forward, the gradient of the rows ``dlhs``,
    the gradient of the weights ``tgmm``), 2*M*K*N apiece."""
    rows = tokens * experts_per_token
    return n_layers * 3 * 3 * 2 * rows * d_model * expert_width


def grouped_gemm_bytes_per_step(tokens, experts_per_token, d_model,
                                expert_width, n_layers, n_experts,
                                itemsize=2):
    """Bytes those nine calls a layer must move if every operand is read
    and every result written exactly once: forward reads rows and
    weights and writes rows; ``dlhs`` reads the cotangent and the weights
    and writes rows; ``tgmm`` reads rows and cotangent and writes the
    weights' gradient. Per matmul with M rows, inner width k, outer
    width n and E experts: 3*(M*k + M*n + E*k*n) elements."""
    rows = tokens * experts_per_token
    per_matmul = 3 * (rows * d_model + rows * expert_width
                      + n_experts * d_model * expert_width)
    return n_layers * 3 * per_matmul * itemsize


def grouped_gemm_floor_s(device_kind, flops, nbytes):
    """The least time the chip could take for that work: the larger of
    FLOPs over the published bf16 peak and bytes over the published HBM
    bandwidth, and which of the two binds."""
    compute = flops / peaks.peak(device_kind)
    memory = nbytes / peaks.peak(device_kind, "hbm_bytes_per_s")
    return max(compute, memory), "compute" if compute >= memory \
        else "memory"
