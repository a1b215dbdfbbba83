"""Llama-family decoder-only transformer, TPU-first.

Design choices (vs a torch translation):
- functional: params are a plain pytree; init/forward are pure functions
  compatible with jit/grad/shard_map.
- scan-over-layers: per-layer params are stacked on a leading axis and the
  dense decoder body is one ``lax.scan`` — O(1) XLA program size in depth,
  the standard TPU idiom (compile time does not grow with n_layers). A
  stack whose layers feed the stacked weights to an opaque kernel (the
  grouped expert GEMMs) runs unrolled instead: ``_run_layers``.
- remat: each scanned layer is wrapped in ``jax.checkpoint`` so activations
  are recomputed in backward — HBM for FLOPs, the right TPU trade.
- bfloat16 compute; params stored in ``param_dtype`` (float32 default
  for stability, bfloat16 for the pure-bf16 large-model recipe — the
  HBM ceiling on a single chip); logits-softmax always float32.
- attention dispatches to exact ring attention when the mesh has a
  non-trivial ``seq`` axis (long-context sequence parallelism), else to
  single-device flash-style blockwise attention.
- sharding by PartitionSpec rules (megatron TP + FSDP), applied by the
  caller via ``llama_partition_rules``; XLA/GSPMD inserts the collectives.
"""

import collections
import dataclasses
import typing
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.ring_attention import ring_self_attention
from horovod_tpu.utils.spans import scope

# Residual names of ops/grouped_moe.py and ``moe_route``: what
# "attn+moe" saves beyond "attn", and what "moe" saves beyond that. The
# sorted order is saved WITH the top-k choice it was sorted from: every
# discrete value of the backward (group sizes, the slot an expert's gate
# gradient belongs to) then derives from the forward's choice and not
# from a recomputed one, which may round a near-tie the other way
# (``_top_k``).
_MOE_SAVE = ("moe_perm", "moe_w_sorted", "moe_gate_idx")
_MOE_EXTRA_SAVE = ("moe_gate_act", "moe_up_act")
# The leaves the grouped GEMMs read: an unrolled stack hands them over
# whole (``grouped_moe.LayerOfStack``), never sliced.
_EXPERT_MATRICES = ("moe_gate", "moe_up", "moe_down")


class LayerSpec(typing.NamedTuple):
    """One layer of ``LlamaConfig.layer_plan``: WHERE its parameters
    live (entry ``index`` of the stack ``params[stack]``) and WHAT it
    runs (its token ``mixer``, "attention", "conv", "linear", "mamba" or
    "mamba2"; a dense or an expert FFN; an attention layer's window, 0 =
    none, and whether it carries RoPE). A layer of ONE part
    (``one_part_layers``) has no ``mixer`` or no FFN: ``mixer`` is None,
    or ``dense_ffn`` is."""
    stack: str
    index: int
    mixer: str
    dense_ffn: bool
    window: int
    rope: bool

    @property
    def kind(self):
        """What tells this layer's PROGRAM from another's."""
        return self[2:]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Rematerialization: True/"full" recomputes the whole layer in
    # backward (min HBM, ~1/3 extra FLOPs); "attn" saves only the flash
    # kernel's residuals; "attn/ffn" the same with the mixer (a
    # linear_attention mixer's two stages) and the FFN recomputed one
    # after the other, not together; "attn+gate" also saves the
    # pre-silu FFN gate (skips one matmul re-run per layer — best
    # measured MFU at bench shapes); "attn+ffn" saves both
    # up-projections (more HBM); "dots"
    # saves every matmul output and recomputes only elementwise work;
    # False/"none" saves everything.
    remat: "bool | str" = True
    # Sparse mixture-of-experts (mixtral-style): n_experts == 0 keeps the
    # dense FFN; otherwise every layer's FFN becomes top-k-routed experts
    # sharded over the mesh's "expert" axis.
    n_experts: int = 0
    n_experts_per_token: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Renormalise the K chosen router probabilities to sum to 1
    # (mixtral) or use them as the softmax gave them (OLMoE publishes
    # ``norm_topk_prob: false``).
    norm_topk_prob: bool = True
    # RMSNorm of the q and k projections, each over its WHOLE projected
    # width (one gain vector per projection and layer), before the split
    # into heads and RoPE: OLMoE's attention. Adds the ``q_norm`` /
    # ``k_norm`` leaves; off, the parameter tree has neither.
    # ``"head"``: the norm runs over EACH head's ``head_dim`` after the
    # split, one gain of ``head_dim`` a projection and layer, shared by
    # the heads (afmoe).
    qk_norm: "bool | str" = False
    # Expert dispatch implementation: "grouped" = dropless sorted
    # grouped-GEMM (megablox; no capacity padding, no one-hot dispatch
    # einsums, no dropped tokens — fastest on a single program),
    # "gshard" = capacity-factor one-hot einsum dispatch (the [G,E,C,D]
    # buffers give GSPMD its expert-parallel all-to-all seam), "auto" =
    # grouped when no mesh is active, gshard under a mesh.
    moe_impl: str = "auto"
    # GPipe microbatch count when the mesh has a non-trivial "pipe" axis
    # (0 = one microbatch per stage). Batch must divide by it.
    pipeline_microbatches: int = 0
    # Pipeline schedule for TRAINING: "gpipe" (all forwards, then AD's
    # reversed backward — per-stage activation stash grows with M),
    # "1f1b" (lockstep forward/backward slots, loss fused into the last
    # stage, stash bounded by ~2S microbatch inputs — see
    # parallel.pipeline.one_f_one_b), or "interleaved_1f1b" (each
    # device holds pipeline_virtual_stages NON-contiguous layer chunks;
    # single-subtick slots cut the bubble to 2(S-1)/(2MV + 2(S-1)),
    # ~V-fold below 1f1b — parallel.pipeline.interleaved_one_f_one_b).
    # Forward-only calls (llama_forward) always use gpipe: the fused
    # schedules never materialize logits. Value-only llama_loss calls
    # (eval loops, loss logging without grad) also run the gpipe
    # forward + loss head under both 1F1B variants — their combined
    # forward/backward computes every gradient just to discard them
    # (~3x the needed work), so only jax.grad/value_and_grad engages
    # them.
    pipeline_schedule: str = "gpipe"
    # Virtual chunks per device for "interleaved_1f1b" (Megatron's
    # virtual pipeline size). n_layers must divide by
    # pipe_size * pipeline_virtual_stages; 1 = the true non-interleaved
    # 1F1B through the same single-subtick engine.
    pipeline_virtual_stages: int = 1
    # Sequence-parallel strategy when the mesh's "seq" axis is
    # non-trivial: "ring" (K/V rotate via ppermute — any head count) or
    # "ulysses" (all-to-all head/sequence reshard — needs
    # n_heads % seq_size == 0, cheaper at short per-device sequences).
    seq_parallel: str = "ring"
    # Pallas flash-attention block size (both the q and k grid blocks;
    # 0 = the kernel default, 1024 — the measured optimum of
    # {256,512,1024,2048}² at t2048, docs/benchmarks.md r4). Exposed so
    # a sweep on the chip can re-try the attention block shapes when the
    # geometry moves; ring/ulysses SP paths keep their own defaults.
    flash_block: int = 0
    # Parameter STORAGE dtype ("float32" default). "bfloat16" halves
    # parameter/gradient/optimizer-state HBM (pure-bf16 training, the
    # usual large-model recipe on TPU) — on one 16G chip it is what
    # lets >1B-param configs fit; use fp32 when running few-hundred-M
    # models where master-precision weights are free.
    param_dtype: str = "float32"
    # --- Published keys of architectures beyond the uniform decoder.
    # Each is off at its default, and a configuration that leaves all of
    # them there builds the parameter tree and the program it always
    # did. Training only: models/generate.py refuses them
    # (``training_only_fields``).
    # Width of one head where it is not d_model / n_heads (``head_dim``
    # in config.json; 0 = the quotient).
    d_head: int = 0
    # Sliding-window attention: a layer of type ``sliding_attention``
    # sees its last ``sliding_window`` keys (itself included).
    # ``layer_types`` names each layer's token mixer as Hugging Face's
    # configs do: ``"sliding_attention"`` = the window AND RoPE,
    # ``"full_attention"`` = every earlier key, with RoPE where
    # ``rope_full_attention`` says so (lfm2) and with NO position
    # encoding where not (afmoe; the default), ``"conv"`` = no attention
    # at all but a gated short convolution over the last ``conv_taps``
    # positions (``_short_conv``; lfm2's ``conv_L_cache``),
    # ``"linear_attention"`` = a Gated DeltaNet mixer
    # (``_gated_delta_net``: the ``linear_*`` sizes below; its depthwise
    # convolution has ``conv_taps`` taps, qwen3_next's
    # ``linear_conv_kernel_dim``).
    # Empty: every layer full, with RoPE (the llama family).
    sliding_window: int = 0
    layer_types: tuple = ()
    rope_full_attention: bool = False
    conv_taps: int = 0
    # The first ``n_dense_layers`` layers of a sparse-expert model keep
    # the dense FFN of width ``d_ff`` (``num_dense_layers``); the expert
    # layers' experts are ``moe_d_ff`` wide (``moe_intermediate_size``;
    # 0 = ``d_ff``, as mixtral and OLMoE publish it). Their parameters
    # are stacked apart: ``params["dense_layers"]`` before
    # ``params["layers"]`` (``layer_plan`` is the rule).
    n_dense_layers: int = 0
    moe_d_ff: int = 0
    # Experts every token passes beside its routed ones
    # (``num_shared_experts``): one SwiGLU of width n x moe_d_ff.
    n_shared_experts: int = 0
    # Router: ``score_func`` "softmax" or "sigmoid" over all experts.
    # With "sigmoid" a layer has an ``expert_bias`` leaf (float32 [E],
    # no gradient reaches it): the K experts are chosen by score + bias
    # and weighted by the score alone, times ``route_scale``.
    score_func: str = "softmax"
    route_scale: float = 1.0
    # ``x = embed[tokens] * sqrt(d_model)`` (``mup_enabled``).
    scale_embed: bool = False
    # Attention's output is gated before ``wo``: ``a * sigmoid(h @ wg)``.
    attn_gate: bool = False
    # Where a layer's norms sit. False: on each part's INPUT
    # (``attn_norm`` or the mixer's own, ``mlp_norm``). True: four
    # norms a layer, the attention's and the FFN's OUTPUT pass an
    # RMSNorm of their own besides (``post_attn_norm``,
    # ``post_mlp_norm``) before joining the residual stream. ``"only"``:
    # the output norms ALONE, ``x + norm(part(x))`` (OLMo 2's reordered
    # norm, arXiv:2501.00656): the tree has no ``attn_norm``,
    # ``gdn_norm`` or ``mlp_norm`` leaf; attention and
    # ``linear_attention`` mixers.
    post_norm: "bool | str" = False
    # The share of an expert-parallel deployment this program holds:
    # experts ``first_expert .. first_expert + n_experts_held - 1`` of
    # each expert layer (0 held = all). The router scores and chooses
    # over all ``n_experts``; the layer computes the shared expert plus
    # the chosen experts it holds, and what the absent ones would add is
    # left out (ops/grouped_moe.py; grouped dispatch only).
    first_expert: int = 0
    n_experts_held: int = 0
    # The head IS the embedding matrix (``tie_embedding``): one leaf,
    # ``embed`` [vocab, d_model], read by the lookup and by the logits;
    # its gradient is the sum of both uses. No ``lm_head`` leaf.
    tie_embeddings: bool = False
    # A ``linear_attention`` layer's heads (qwen3_next's
    # ``linear_num_key_heads``, ``linear_num_value_heads``,
    # ``linear_key_head_dim``, ``linear_value_head_dim``): a key head
    # serves value_heads / key_heads consecutive value heads.
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    # The largest write strength of a ``linear_attention`` layer's delta
    # rule: ``beta = linear_beta_max * sigmoid(b)``. 1: qwen3_next's; 2
    # (``linear_allow_neg_eigval``): with unit keys the write's
    # transition ``I - beta k k^T`` then has its eigenvalue along ``k``
    # in (-1, 1) instead of (0, 1) (arXiv:2411.12537).
    linear_beta_max: float = 1.0
    # RoPE turns the FIRST ``partial_rotary`` dimensions of a head and
    # passes the rest (``partial_rotary_factor`` x ``head_dim``; 0 = the
    # whole head).
    partial_rotary: int = 0
    # The shared expert's output is gated: ``sigmoid(h @ shared_score)``
    # (``shared_score`` [D, 1]) times its SwiGLU.
    shared_expert_gate: bool = False
    # A ``mamba`` layer (``layer_types``; jamba's Mamba-1 mixer,
    # ``_mamba``): ``mamba_d_state`` states a channel, the step size
    # projected through ``mamba_dt_rank``, ``mamba_expand`` x d_model
    # channels, a depthwise convolution of ``conv_taps`` taps
    # (``mamba_d_conv``) with a bias where ``mamba_conv_bias``.
    mamba_d_state: int = 0
    mamba_dt_rank: int = 0
    mamba_expand: int = 0
    mamba_conv_bias: bool = False
    # Tokens a block of the loss's head (``llama_loss`` only): the
    # float32 logits, their logsumexp and the picked logit are computed
    # a block at a time under a checkpoint, so that no [B, T, vocab]
    # array exists, forward or backward. 0: whole logits.
    # ``llama_forward`` returns whole logits whatever this says.
    loss_chunk: int = 0
    # A layer is ONE part, a token mixer or a feed-forward part, under
    # one norm and one residual add (nemotron_h's
    # ``hybrid_override_pattern``): ``layer_types`` then holds
    # ``"mamba2"`` and ``"full_attention"`` (a mixer and NO FFN) and
    # ``"experts"`` (an expert layer and NO mixer), each stack holds its
    # own leaves only (``layer_plan``). Off, every layer is a mixer and
    # an FFN.
    one_part_layers: bool = False
    # A ``mamba2`` layer (``_mamba2``; Mamba-2's SSD mixer):
    # ``ssd_heads`` heads of ``ssd_head_dim`` channels, ``ssd_state``
    # states, ``B`` / ``C`` shared by the heads of each of
    # ``ssd_groups`` groups, the recurrence in chunks of ``ssd_chunk``
    # tokens (``ops/ssd.py``); its convolution has ``conv_taps`` taps,
    # with a bias where ``mamba_conv_bias``.
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_state: int = 0
    ssd_groups: int = 0
    ssd_chunk: int = 0
    # The feed-forward parts' form, dense, shared and routed alike:
    # ``"swiglu"`` (three matrices, ``(silu(h Wg) * h Wu) Wd``) or
    # ``"relu2"`` (two, ``relu(h Wu)^2 Wd``: no gate leaf exists).
    ffn_act: str = "swiglu"
    # The ROUTED experts work in a space ``moe_latent`` wide (0: in
    # ``d_model``), behind ``moe_lat_down`` [D, l] and in front of
    # ``moe_lat_up`` [l, D], one pair an expert layer; the router and the
    # shared expert read the ``d_model``-wide input.
    moe_latent: int = 0
    # The shared expert's width where it is not ``n_shared_experts x
    # moe_d_ff`` (``moe_shared_expert_intermediate_size``; 0 = that).
    shared_d_ff: int = 0
    # Multi-token prediction, training's form (DeepSeek-V3, section
    # 2.2): ``mtp_layers`` modules (one is implemented) of the layer
    # types ``mtp_types`` under ``params["mtp"]`` read the main model's
    # last hidden state and the NEXT token's embedding and predict the
    # token after it through the main model's head; ``llama_loss`` adds
    # ``mtp_weight`` times that cross-entropy.
    mtp_layers: int = 0
    mtp_types: tuple = ()
    mtp_weight: float = 0.0
    # muP's three scalings as MiniCPM publishes them (``scale_emb``,
    # ``scale_depth / sqrt(num_hidden_layers)``, ``hidden_size /
    # dim_model_base``), numbers of the model and no switches: the
    # embedding times ``embed_mult`` (0: ``scale_embed`` decides), what
    # a mixer or an FFN adds to the stream times ``residual_mult``, the
    # final norm's output over ``logit_div`` in front of the head.
    embed_mult: float = 0.0
    residual_mult: float = 1.0
    logit_div: float = 1.0
    # Tokens a block of the dense SwiGLU (``_ffn``): the three [tokens,
    # d_ff] activations exist a block at a time under a checkpoint,
    # forward and backward, as ``loss_chunk``'s logits. 0: whole.
    ffn_chunk: int = 0
    # A ``sparse_attention`` layer (minicpm_sala's ``minicpm4`` mixer:
    # InfLLM-V2; ``ops/sparse_attention.py`` has the five steps): the
    # attention layer's leaves and projections (``qk_norm``,
    # ``attn_gate`` as set; no RoPE), each token attending at most
    # ``sparse_topk`` blocks of ``sparse_block`` keys a key/value group,
    # chosen by scores against keys pooled ``sparse_kernel`` wide every
    # ``sparse_stride``, the first ``sparse_init_blocks`` and the last
    # ``sparse_window_blocks`` begun blocks always among them. A
    # sequence of up to ``sparse_dense_len`` tokens runs the layer DENSE
    # (the published ``dense_len``): plain causal attention.
    sparse_block: int = 0
    sparse_topk: int = 0
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_init_blocks: int = 0
    sparse_window_blocks: int = 0
    sparse_dense_len: int = 0
    # A ``lightning_attention`` layer (minicpm_sala's ``lightning-attn``
    # mixer, ``_lightning``): ``lightning_heads`` heads of
    # ``lightning_head_dim`` with a key and a value head each, a state
    # a head that decays by a constant of the layer and head
    # (``lightning_rates``: ALiBi's slopes times a factor that falls
    # with the layer's place among ``lightning_depth`` PUBLISHED layers,
    # 0 = ``n_layers``), the recurrence in chunks of ``lightning_chunk``
    # tokens (``ops/ssd.py``).
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_chunk: int = 0
    lightning_depth: int = 0
    # Multi-head latent attention (DeepSeek-V2, section 2.1; the
    # training form: keys and values built a head from the latent), the
    # mixer of EVERY attention layer where ``kv_lora_rank`` is set: the
    # query through a latent ``q_lora_rank`` wide and the keys and
    # values through one ``kv_lora_rank`` wide, each latent under an
    # RMSNorm; a head's query and key are ``qk_nope_head_dim`` values
    # without a position beside ``qk_rope_head_dim`` rotated ones, the
    # rotated key ONE for all heads; a head's value is ``v_head_dim``
    # wide (``_latent_attention``; ``d_head`` and ``n_kv_heads`` are not
    # read). ``rope_yarn``: the rotated slice's frequencies and the
    # softmax scale under YaRN, ``(factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim)``; empty: ``rope_theta``'s own and ``1 / sqrt(qk
    # width)``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: tuple = ()
    # Manifold-constrained hyper-connections (arXiv 2512.24880): the
    # residual stream is ``hc_mult`` streams a token (0: one, ``x +
    # part(norm x)``), [B, n, T, D]; round every part (a mixer or a
    # feed-forward part with its pre-norm) three sets of coefficients a
    # token, from the RMS-normed streams through learned projections,
    # say what the part reads (``sigmoid``), where its output is added
    # (``2 sigmoid``) and how the streams mix: a doubly stochastic ``n x
    # n`` matrix, ``hc_sinkhorn_iters`` Sinkhorn-Knopp iterations (sums
    # guarded by ``hc_eps``) over ``exp`` of the logits clamped to
    # ``hc_clamp`` (``_hyper_connection``). The stream starts as ``n``
    # copies of the embedding and ends as their sum.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: tuple = ()
    # A looped decoder (LoopLM: Ouro, arXiv 2510.25741 section 3;
    # ``total_ut_steps``): the ONE stack of layers and the final norm
    # run ``loop_steps`` times with the same weights, a trip's output
    # the next trip's input (1: a plain decoder, no leaf and no
    # instruction more). After EVERY trip an exit: the head's logits and
    # a gate a token, ``sigmoid(h . exit_gate_w + exit_gate_b)``, the
    # share of what has not left yet that leaves here; the last trip
    # takes the rest. ``llama_loss`` is the expectation of the exits'
    # cross-entropies under that distribution less
    # ``exit_entropy_weight`` times its entropy (``_exit_loss``);
    # ``llama_forward`` gives the last trip's logits.
    loop_steps: int = 1
    exit_entropy_weight: float = 0.0

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        types = self.layer_types + self.mtp_types
        one_part = ("mamba2", "full_attention", "experts")
        if any(t not in (one_part if self.one_part_layers else (
                "sliding_attention", "full_attention", "conv",
                "linear_attention", "mamba", "sparse_attention",
                "lightning_attention")) for t in types):
            raise ValueError(
                f"unknown layer type in {types}: with one_part_layers "
                f"{one_part}, without it no 'mamba2' and no 'experts'")
        sizes = (self.sparse_block, self.sparse_topk, self.sparse_kernel,
                 self.sparse_stride)
        if ("sparse_attention" in types) != all(sizes) or (
                "sparse_attention" not in types and any(
                    sizes + (self.sparse_init_blocks,
                             self.sparse_window_blocks,
                             self.sparse_dense_len))):
            raise ValueError(
                "sparse_attention layers and the selection's sizes "
                "(sparse_block, sparse_topk, sparse_kernel, "
                f"sparse_stride) come together: {sizes}")
        sizes = (self.lightning_heads, self.lightning_head_dim,
                 self.lightning_chunk)
        if ("lightning_attention" in types) != all(sizes) or (
                "lightning_attention" not in types
                and any(sizes + (self.lightning_depth,))):
            raise ValueError(
                "lightning_attention layers and their sizes "
                "(lightning_heads, lightning_head_dim, lightning_chunk) "
                f"come together: {sizes}")
        if self.ffn_chunk < 0:
            raise ValueError(f"ffn_chunk {self.ffn_chunk}: tokens a block "
                             "of the dense FFN, 0 for whole")
        sizes = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                 self.qk_rope_head_dim, self.v_head_dim)
        if any(sizes) != all(sizes) or self.qk_rope_head_dim % 2 or (
                self.rope_yarn and not (self.kv_lora_rank
                                        and len(self.rope_yarn) == 6)):
            raise ValueError(
                "latent attention's five sizes (q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim, v_head_dim) come "
                f"together, the rotated slice in pairs: {sizes}; rope_yarn "
                "is its six numbers, or empty")
        if self.kv_lora_rank and (
                self.qk_norm or self.attn_gate or self.partial_rotary
                or self.sliding_window or any(
                    t != "full_attention" for t in types)):
            raise ValueError(
                "latent attention is every layer's mixer, with norms on "
                "its two latents and its own rotated slice: no qk_norm, "
                "attn_gate, partial_rotary, window or other mixer")
        sizes = (self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
                 len(self.hc_clamp) == 2)
        if any(sizes) != all(sizes) or self.hc_mult == 1:
            raise ValueError(
                "hyper-connections' sizes (hc_mult > 1, hc_sinkhorn_iters, "
                f"hc_eps, hc_clamp's two edges) come together: {sizes}")
        linear = "linear_attention" in types
        mamba, mamba2 = "mamba" in types, "mamba2" in types
        if ("conv" in types or linear or mamba or mamba2) \
                != (self.conv_taps > 0):
            raise ValueError("conv, linear_attention, mamba and mamba2 "
                             "layers and conv_taps come together: "
                             f"{types}, {self.conv_taps} taps")
        sizes = (self.mamba_d_state, self.mamba_dt_rank, self.mamba_expand)
        if mamba != all(sizes) or (not mamba and any(sizes)) or (
                self.mamba_conv_bias and not (mamba or mamba2)):
            raise ValueError(
                "mamba layers and their three sizes (mamba_d_state, "
                f"mamba_dt_rank, mamba_expand) come together: {sizes}: "
                "the mixer's projections and its state have no other "
                "source")
        sizes = (self.ssd_heads, self.ssd_head_dim, self.ssd_state,
                 self.ssd_groups, self.ssd_chunk)
        if mamba2 != all(sizes) or (not mamba2 and any(sizes)):
            raise ValueError(
                "mamba2 layers and their five sizes (ssd_heads, "
                "ssd_head_dim, ssd_state, ssd_groups, ssd_chunk) come "
                f"together: {sizes}: the mixer's projections and its "
                "state have no other source")
        if mamba2 and self.ssd_heads % self.ssd_groups:
            raise ValueError(
                f"{self.ssd_heads} ssd heads are no multiple of "
                f"{self.ssd_groups} groups: each group's B and C serve a "
                "whole number of heads")
        if self.one_part_layers and "experts" in types \
                and not self.n_experts:
            raise ValueError("an 'experts' layer routes over n_experts: 0")
        if self.one_part_layers and self.n_dense_layers:
            raise ValueError("one_part_layers names every layer's part in "
                             "layer_types: no n_dense_layers")
        if self.one_part_layers and not self.layer_types:
            raise ValueError("one_part_layers names every layer's part in "
                             "layer_types: empty")
        if self.ffn_act not in ("swiglu", "relu2"):
            raise ValueError(f"unknown ffn_act {self.ffn_act!r}")
        if (self.moe_latent or self.shared_d_ff) and not self.n_experts:
            raise ValueError("moe_latent and shared_d_ff size an expert "
                             "layer: n_experts is 0")
        if self.shared_d_ff and not self.n_shared_experts:
            raise ValueError("shared_d_ff is the shared expert's width: "
                             "n_shared_experts is 0")
        if self.mtp_layers not in (0, 1) or bool(self.mtp_layers) != bool(
                self.mtp_types) or bool(self.mtp_layers) != (
                self.mtp_weight > 0):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}, mtp_types "
                f"{self.mtp_types} and mtp_weight {self.mtp_weight} come "
                "together, for ONE module (modules that share weights "
                "over depths are not implemented)")
        if self.loop_steps < 1 or (self.loop_steps == 1
                                   and self.exit_entropy_weight) or (
                self.loop_steps > 1 and (self.n_experts
                                         or self.mtp_layers)):
            raise ValueError(
                f"loop_steps {self.loop_steps} (exit_entropy_weight "
                f"{self.exit_entropy_weight}): trips of ONE dense stack, "
                "at least one; the entropy is of the exits of more than "
                "one; expert layers' balance statistics a trip and the "
                "MTP module's stream have no place in the exits' loss")
        if self.loss_chunk < 0:
            raise ValueError(f"loss_chunk {self.loss_chunk}: tokens a "
                             "block of the loss's head, 0 for whole "
                             "logits")
        sizes = (self.linear_key_heads, self.linear_value_heads,
                 self.linear_key_dim, self.linear_value_dim)
        if linear != all(sizes) or (not linear and any(sizes)):
            raise ValueError(
                "linear_attention layers and their four sizes "
                "(linear_key_heads, linear_value_heads, linear_key_dim, "
                f"linear_value_dim) come together: {sizes}: the mixer's "
                "projections and its state have no other source")
        if linear and self.linear_value_heads % self.linear_key_heads:
            raise ValueError(
                f"{self.linear_value_heads} value heads are no multiple "
                f"of {self.linear_key_heads} key heads: each key head "
                "serves a whole number of value heads")
        if self.linear_beta_max != 1.0 and not (
                linear and 0.0 < self.linear_beta_max <= 2.0):
            raise ValueError(
                f"linear_beta_max {self.linear_beta_max}: the largest "
                "write strength of a linear_attention layer's delta rule "
                "(there is none without such a layer), in (0, 2]: past 2 "
                "a write's transition I - beta k k^T grows along k")
        if self.post_norm not in (False, True, "only"):
            raise ValueError(f"unknown post_norm {self.post_norm!r}")
        if self.post_norm == "only" and (
                self.kv_lora_rank or self.hc_mult or self.one_part_layers
                or any(t not in ("full_attention", "sliding_attention",
                                 "linear_attention") for t in types)):
            raise ValueError(
                "post_norm 'only' norms the OUTPUT of an attention or "
                "linear_attention mixer and of the FFN: latent attention "
                "norms its latents behind an input norm, the other "
                "mixers (conv, mamba, mamba2, sparse, lightning) norm "
                "their input inside their own programs, a hyper-"
                "connection part reads a blend whose scale no output "
                "norm sets, and a layer of one part has one norm, its "
                f"input's: {types or 'full_attention'}")
        if self.partial_rotary % 2 or self.partial_rotary > self.head_dim:
            raise ValueError(
                f"partial_rotary {self.partial_rotary}: RoPE turns pairs "
                f"of dimensions, at most a head's {self.head_dim}")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate gates a shared expert: "
                             "n_shared_experts is 0")
        if "sliding_attention" in self.layer_types \
                and self.sliding_window <= 0:
            raise ValueError("sliding_attention layers need a "
                             "sliding_window")
        if self.n_dense_layers and not (
                self.n_experts > 0
                and self.n_dense_layers < self.n_layers):
            raise ValueError("n_dense_layers counts the leading dense "
                             "layers of a sparse-expert model: fewer "
                             "than n_layers, with n_experts > 0")
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown score_func {self.score_func!r}")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}")
        if (self.first_expert or self.n_experts_held) and not (
                0 <= self.first_expert
                and 0 < self.n_experts_held
                and self.first_expert + self.n_experts_held
                <= self.n_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.n_experts_held} "
                f"are no share of {self.n_experts}")

    @property
    def head_dim(self):
        return self.d_head or self.d_model // self.n_heads

    @property
    def qk_head_dim(self):
        """The width of a latent-attention head's query and key."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def yarn(self):
        """Latent attention's rotation and scale under ``rope_yarn``
        (Hugging Face's DeepSeek-V3 form) -> (``inv_freq`` float32
        [qk_rope_head_dim / 2], what cos and sin are multiplied by, the
        softmax scale). ``inv_freq`` blends ``theta^(-2i/d)`` (kept
        where a dimension turns more than ``beta_fast`` times in the
        original length) and the same over ``factor`` (where fewer than
        ``beta_slow``) by a linear ramp between the two correction
        dimensions; the scale is ``m^2 / sqrt(qk width)``, ``m = 0.1
        mscale_all_dim ln(factor) + 1``."""
        d = self.qk_rope_head_dim
        inv = self.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        scale = self.qk_head_dim ** -0.5
        if not self.rope_yarn:
            return inv.astype(np.float32), 1.0, scale
        factor, original, fast, slow, mscale, all_dim = self.rope_yarn

        def correction_dim(turns):
            return d * np.log(original / (turns * 2 * np.pi)) \
                / (2 * np.log(self.rope_theta))

        def m(scale):
            return 0.1 * scale * np.log(factor) + 1.0 if factor > 1 else 1.0

        low = max(np.floor(correction_dim(fast)), 0)
        high = min(np.ceil(correction_dim(slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low)
                       / ((high if high != low else high + 0.001) - low),
                       0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        return (inv.astype(np.float32), float(m(mscale) / m(all_dim)),
                float(scale * m(all_dim) ** 2 if all_dim else scale))

    @property
    def mamba_d_inner(self):
        """A mamba layer's channels."""
        return self.mamba_expand * self.d_model

    @property
    def ssd_d_inner(self):
        """A mamba2 layer's channels."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def expert_width(self):
        return self.moe_d_ff or self.d_ff

    @property
    def shared_width(self):
        """The shared expert's width."""
        return self.shared_d_ff or self.n_shared_experts * self.expert_width

    @property
    def expert_d_in(self):
        """The width of the rows the routed experts read and write."""
        return self.moe_latent or self.d_model

    @property
    def experts_here(self):
        """How many experts' weights a layer of this program holds."""
        return self.n_experts_held or self.n_experts

    def layer_plan(self, mtp=False):
        """One :data:`LayerSpec` a layer, in order (``mtp``: a layer of
        the MTP module, ``mtp_types``, whose stacks lie under
        ``params["mtp"]``): the ONE rule for
        where a layer's parameters live and which program it runs, read
        by ``llama_init``, ``_run_layers`` and whatever walks the tree
        (the references, the benchmark's adapters). Layers with the same
        leaves are stacked together: ``layers`` (attention; the only
        stack of a uniform model), ``conv_layers`` (a conv layer has
        ``conv_in``, ``conv_w``, ``conv_out`` and no ``wq`` .. ``wo``),
        ``linear_layers`` (a Gated DeltaNet layer: the ``gdn_*`` leaves),
        ``mamba_layers`` (a Mamba layer: the ``ssm_*`` leaves; a stack
        a RUN of consecutive mamba layers, the second run's
        ``mamba_1_layers`` and so on, so that each run is a whole stack
        and ``_run_layers`` can scan it), ``sparse_layers`` (attention's
        leaves, read by the block-sparse mixer), ``lightning_layers``
        (``wq`` .. ``wo`` at the lightning heads' width, ``out_norm``,
        ``wg``), and the leading dense layers
        of a sparse-expert model apart as
        ``dense_layers`` / ``dense_conv_layers``. Under
        ``one_part_layers`` a layer is a mixer alone (``dense_ffn``
        None: ``mamba2_layers`` with the ``ssd_*`` leaves, ``layers``
        with attention's and no FFN's) or an expert layer alone
        (``mixer`` None: ``expert_layers`` with the router's, the
        experts' and the shared expert's leaves and no ``wq`` ..
        ``wo``), under its one norm. Every name ends in
        ``layers``: ``llama_partition_rules`` shards them alike."""
        plan, filled = [], collections.Counter()
        runs = -1              # of mamba layers, so far
        types = self.mtp_types if mtp else self.layer_types
        for i in range(len(types) if mtp else self.n_layers):
            kind = types[i] if types else "full_attention"
            sliding = kind == "sliding_attention"
            mixer = {"conv": "conv", "linear_attention": "linear",
                     "mamba": "mamba", "mamba2": "mamba2",
                     "sparse_attention": "sparse",
                     "lightning_attention": "lightning",
                     "experts": None}.get(kind, "attention")
            # (the leading dense layers are the MODEL's: none in the MTP
            # module, which no configuration had beside them before)
            leading = not mtp and i < self.n_dense_layers
            dense_ffn = self.n_experts == 0 or leading
            stack = ("dense_" if leading else "") \
                + ("" if mixer == "attention" else f"{mixer}_") + "layers"
            if self.one_part_layers:
                dense_ffn = None if mixer else False
                stack = stack if mixer else "expert_layers"
            if mixer == "mamba":
                runs += not plan or (plan[-1].mixer, plan[-1].dense_ffn) \
                    != (mixer, dense_ffn)
                if runs:
                    stack = stack.replace("mamba_", f"mamba_{runs}_")
            plan.append(LayerSpec(
                stack, filled[stack], mixer, dense_ffn,
                self.sliding_window if sliding else 0,
                mixer == "attention" and (
                    sliding or not types or self.rope_full_attention)))
            filled[stack] += 1
        return plan

    def lightning_rates(self, stack):
        """The decay rates of the lightning layers of ``stack``, [its
        layers, heads] float32, negative: ``-s_n f_l`` with ``s_n = 2^(-8
        (n + 1) / H)`` (Lightning Attention's slopes, arXiv:2401.04658)
        and ``f_l = 1 - l / (L - 1) + 1e-5`` (MiniMax-01's layer factor,
        arXiv:2501.08313), ``l`` the layer's place in ``layer_types`` and
        ``L`` the PUBLISHED depth. Constants: no leaf holds them and no
        gradient reaches them."""
        at = np.array([i for i, spec in enumerate(self.layer_plan())
                       if spec.stack == stack], np.float64)
        H, L = self.lightning_heads, self.lightning_depth or self.n_layers
        slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
        return -np.outer(1.0 - at / max(L - 1, 1) + 1e-5, slopes).astype(
            np.float32)

    def layer_kinds(self):
        """One ``(dense_ffn, window, rope)`` a layer, in order: what
        told the layer programs of a model apart while every layer's
        mixer was attention, and what the benchmark's accepted adapter
        still reads (chipbench/models/afmoe.py). ``layer_plan`` has the
        mixer too."""
        return [(spec.dense_ffn, spec.window, spec.rope)
                for spec in self.layer_plan()]

    def training_only_fields(self):
        """Names of the fields set here that only the training path
        (``llama_forward`` / ``llama_loss``) implements; decode, serving
        and the pipeline schedules refuse a configuration with any."""
        d = LlamaConfig()
        return [f for f in ("d_head", "sliding_window", "layer_types",
                            "n_dense_layers", "moe_d_ff",
                            "n_shared_experts", "score_func",
                            "route_scale", "scale_embed", "attn_gate",
                            "post_norm", "first_expert", "n_experts_held",
                            "rope_full_attention", "conv_taps",
                            "tie_embeddings", "linear_key_heads",
                            "linear_value_heads", "linear_key_dim",
                            "linear_value_dim", "linear_beta_max",
                            "partial_rotary",
                            "shared_expert_gate", "mamba_d_state",
                            "mamba_dt_rank", "mamba_expand",
                            "mamba_conv_bias", "loss_chunk",
                            "one_part_layers", "ssd_heads", "ssd_head_dim",
                            "ssd_state", "ssd_groups", "ssd_chunk",
                            "ffn_act", "moe_latent", "shared_d_ff",
                            "mtp_layers", "mtp_types", "mtp_weight",
                            "embed_mult", "residual_mult", "logit_div",
                            "ffn_chunk", "sparse_block", "sparse_topk",
                            "sparse_kernel", "sparse_stride",
                            "sparse_init_blocks", "sparse_window_blocks",
                            "sparse_dense_len", "lightning_heads",
                            "lightning_head_dim", "lightning_chunk",
                            "lightning_depth", "q_lora_rank",
                            "kv_lora_rank", "qk_nope_head_dim",
                            "qk_rope_head_dim", "v_head_dim", "rope_yarn",
                            "hc_mult", "hc_sinkhorn_iters", "hc_eps",
                            "hc_clamp", "loop_steps",
                            "exit_entropy_weight")
                if getattr(self, f) != getattr(d, f)] \
            + (["qk_norm"] if self.qk_norm == "head" else [])

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @staticmethod
    def llama3_8b():
        return LlamaConfig(vocab_size=128256, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336)

    @staticmethod
    def mixtral_8x7b():
        return LlamaConfig(vocab_size=32000, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           n_experts=8, n_experts_per_token=2)

    @staticmethod
    def tiny(**kw):
        """Test/dryrun config: full architecture, toy sizes."""
        defaults = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=128, rope_theta=10000.0)
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_moe(**kw):
        """Tiny sparse-MoE variant (expert-parallel test/dryrun config)."""
        kw.setdefault("n_experts", 4)
        return LlamaConfig.tiny(**kw)


def llama_init(config, key):
    """Initialize the parameter pytree (stored in config.param_dtype;
    float32 by default — "master weights" — or bfloat16 for the
    pure-bf16 large-model recipe).

    Per-layer tensors are stacked on a leading axis for scan, one stack
    a kind of layer (``LlamaConfig.layer_plan``): a uniform model has
    ``params["layers"]`` alone; a sparse-expert model's leading dense
    layers and a hybrid's conv layers are stacks of their own.
    """
    c = config
    hd = c.head_dim
    k = iter(jax.random.split(key, 16))
    pd = jnp.dtype(c.param_dtype)

    def dense(key, shape, fan_in):
        # Cast per-leaf at creation: a post-hoc whole-tree cast would
        # transiently hold fp32 AND target trees (~1.5x init peak, which
        # matters for >1B params on a 16G chip).
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(pd)

    def stack(k, x, L, mixer, dense_ffn):
        """``L`` layers of one kind, stacked. ``k`` deals the keys of
        the leaves every configuration has, in the order it always did;
        ``x`` those of the leaves only the newer fields add, so that an
        older configuration's weights do not move."""
        if mixer is None:        # an expert layer alone: its one norm
            layers = {"mlp_norm": jnp.ones((L, c.d_model), pd)}
        elif mixer == "mamba2":
            # [z | x B C | dt] side by side, as Mamba-2 publishes them;
            # B and C a group.
            di, gn = c.ssd_d_inner, c.ssd_groups * c.ssd_state
            mk = iter(jax.random.split(next(x), 2))
            layers = {
                "ssd_norm": jnp.ones((L, c.d_model), pd),
                "ssd_in": dense(next(k), (L, c.d_model,
                                          2 * di + 2 * gn + c.ssd_heads),
                                c.d_model),
                "ssd_conv": dense(next(k), (L, c.conv_taps, di + 2 * gn),
                                  c.conv_taps),
                # Mamba-2's start: A uniform over (1, 16) a head;
                # softplus(ssd_dt_bias) log-uniform over (1e-3, 0.1)
                # (``time_step_min`` .. ``time_step_max``), floored at
                # ``time_step_floor`` 1e-4; D = 1.
                "ssd_a_log": jnp.log(jax.random.uniform(
                    next(mk), (L, c.ssd_heads), jnp.float32, 1.0, 16.0)
                    ).astype(pd),
                "ssd_dt_bias": _softplus_inverse(jnp.maximum(jnp.exp(
                    jax.random.uniform(next(mk), (L, c.ssd_heads),
                                       jnp.float32, jnp.log(1e-3),
                                       jnp.log(0.1))), 1e-4)).astype(pd),
                "ssd_d": jnp.ones((L, c.ssd_heads), pd),
                "ssd_out_norm": jnp.ones((L, di), pd),
                "ssd_out": dense(next(k), (L, di, c.d_model), di),
            }
            if c.mamba_conv_bias:
                layers["ssd_conv_bias"] = jnp.zeros((L, di + 2 * gn), pd)
        elif mixer == "conv":
            layers = {
                "conv_norm": jnp.ones((L, c.d_model), pd),
                "conv_in": dense(next(k), (L, c.d_model, 3 * c.d_model),
                                 c.d_model),
                "conv_w": dense(next(k), (L, c.conv_taps, c.d_model),
                                c.conv_taps),
                "conv_out": dense(next(k), (L, c.d_model, c.d_model),
                                  c.d_model),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
        elif mixer == "linear":
            # [q | k | v | z] and [b | a] side by side, each head by
            # head (qwen3_next's checkpoint groups the same columns by
            # key head: a permutation).
            kw = c.linear_key_heads * c.linear_key_dim
            vw = c.linear_value_heads * c.linear_value_dim
            layers = {
                "gdn_norm": jnp.ones((L, c.d_model), pd),
                "gdn_in": dense(next(k), (L, c.d_model, 2 * kw + 2 * vw),
                                c.d_model),
                "gdn_ba": dense(next(x),
                                (L, c.d_model, 2 * c.linear_value_heads),
                                c.d_model),
                "gdn_conv": dense(next(k), (L, c.conv_taps, 2 * kw + vw),
                                  c.conv_taps),
                # Hugging Face's start: A uniform over (0, 16), dt_bias 1.
                "gdn_a_log": jnp.log(jax.random.uniform(
                    next(x), (L, c.linear_value_heads), jnp.float32,
                    1e-3, 16.0)).astype(pd),
                "gdn_dt_bias": jnp.ones((L, c.linear_value_heads), pd),
                "gdn_out_norm": jnp.ones((L, c.linear_value_dim), pd),
                "gdn_out": dense(next(k), (L, vw, c.d_model), vw),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
        elif mixer == "mamba":
            di, n, r = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
            mk = iter(jax.random.split(next(x), 2))
            layers = {
                "ssm_norm": jnp.ones((L, c.d_model), pd),
                # [u | z] side by side
                "ssm_in": dense(next(k), (L, c.d_model, 2 * di), c.d_model),
                "ssm_conv": dense(next(k), (L, c.conv_taps, di),
                                  c.conv_taps),
                # [dt | B | C] side by side
                "ssm_x": dense(next(k), (L, di, r + 2 * n), di),
                "ssm_dt_norm": jnp.ones((L, r), pd),
                "ssm_b_norm": jnp.ones((L, n), pd),
                "ssm_c_norm": jnp.ones((L, n), pd),
                "ssm_dt": dense(next(mk), (L, r, di), r),
                # softplus(ssm_dt_bias) is log-uniform over (1e-3, 0.1),
                # Mamba's ``dt_min`` .. ``dt_max``: the steps a layer
                # starts from span two decades, a channel each.
                "ssm_dt_bias": _softplus_inverse(jnp.exp(
                    jax.random.uniform(next(mk), (L, di), jnp.float32,
                                       jnp.log(1e-3), jnp.log(0.1)))
                    ).astype(pd),
                # Mamba's S4D-real start: A[c, n] = -(n + 1).
                "ssm_a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32)), (L, di, n)).astype(pd),
                "ssm_d": jnp.ones((L, di), pd),
                "ssm_out": dense(next(k), (L, di, c.d_model), di),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
            if c.mamba_conv_bias:
                layers["ssm_conv_bias"] = jnp.zeros((L, di), pd)
        elif mixer == "lightning":
            # attention's names at the lightning heads' width: a key and
            # a value head a query head, one q/k gain for all heads, the
            # output norm over the concatenation, the output gate.
            ld, hw = c.lightning_head_dim, \
                c.lightning_heads * c.lightning_head_dim
            layers = {
                "attn_norm": jnp.ones((L, c.d_model), pd),
                "wq": dense(next(k), (L, c.d_model, hw), c.d_model),
                "wk": dense(next(k), (L, c.d_model, hw), c.d_model),
                "wv": dense(next(k), (L, c.d_model, hw), c.d_model),
                "wo": dense(next(k), (L, hw, c.d_model), hw),
                "q_norm": jnp.ones((L, ld), pd),
                "k_norm": jnp.ones((L, ld), pd),
                "out_norm": jnp.ones((L, hw), pd),
                "wg": dense(next(x), (L, c.d_model, hw), c.d_model),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
        elif c.kv_lora_rank:
            # latent attention: [q_nope | q_rope] a head behind the
            # query's latent, [c_kv | k_rope] side by side out of the
            # stream, [k_nope | v] a head behind the keys' and values'
            rq, rkv, H = c.q_lora_rank, c.kv_lora_rank, c.n_heads
            layers = {
                "attn_norm": jnp.ones((L, c.d_model), pd),
                "wq_a": dense(next(k), (L, c.d_model, rq), c.d_model),
                "q_a_norm": jnp.ones((L, rq), pd),
                "wq_b": dense(next(x), (L, rq, H * c.qk_head_dim), rq),
                "wkv_a": dense(next(k),
                               (L, c.d_model, rkv + c.qk_rope_head_dim),
                               c.d_model),
                "kv_a_norm": jnp.ones((L, rkv), pd),
                "wkv_b": dense(next(x), (L, rkv, H * (
                    c.qk_nope_head_dim + c.v_head_dim)), rkv),
                "wo": dense(next(k), (L, H * c.v_head_dim, c.d_model),
                            H * c.v_head_dim),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
        else:
            layers = {
                "attn_norm": jnp.ones((L, c.d_model), pd),
                "wq": dense(next(k), (L, c.d_model, c.n_heads * hd),
                            c.d_model),
                "wk": dense(next(k), (L, c.d_model, c.n_kv_heads * hd),
                            c.d_model),
                "wv": dense(next(k), (L, c.d_model, c.n_kv_heads * hd),
                            c.d_model),
                "wo": dense(next(k), (L, c.n_heads * hd, c.d_model),
                            c.n_heads * hd),
                "mlp_norm": jnp.ones((L, c.d_model), pd),
            }
            if c.qk_norm == "head":
                layers["q_norm"] = jnp.ones((L, hd), pd)
                layers["k_norm"] = jnp.ones((L, hd), pd)
            elif c.qk_norm:
                layers["q_norm"] = jnp.ones((L, c.n_heads * hd), pd)
                layers["k_norm"] = jnp.ones((L, c.n_kv_heads * hd), pd)
            if c.attn_gate:
                layers["wg"] = dense(next(x),
                                     (L, c.d_model, c.n_heads * hd),
                                     c.d_model)
        if c.post_norm:
            layers["post_attn_norm"] = jnp.ones((L, c.d_model), pd)
            layers["post_mlp_norm"] = jnp.ones((L, c.d_model), pd)
        if c.post_norm == "only":     # no part's input is normed
            for name in ("attn_norm", "gdn_norm", "mlp_norm"):
                layers.pop(name, None)
        if c.hc_mult:
            # A part's three sets of coefficients side by side, [pre |
            # post | res]: the projections ``phi`` a stream, and in
            # float32 whatever param_dtype (they enter float32
            # arithmetic, 27 numbers a part) the three scalars ``alpha``
            # and the biases. The start: the dynamic terms whole
            # (``alpha`` 1 on projections of unit variance), so that
            # what a part reads and where it writes differ by stream and
            # token, and the mixing matrix leans to the identity
            # (``2 I`` in the logits: 0.7 on the diagonal) without being
            # it.
            n = c.hc_mult
            start = jnp.concatenate([jnp.zeros(2 * n, jnp.float32),
                                     2.0 * jnp.eye(n).ravel()])
            for part in ("attn",) * (mixer is not None) \
                    + ("mlp",) * (dense_ffn is not None):
                layers.update({
                    f"hc_{part}_phi": dense(
                        next(x), (L, n, c.d_model, n * (n + 2)),
                        n * c.d_model),
                    f"hc_{part}_alpha": jnp.ones((L, 3), jnp.float32),
                    f"hc_{part}_bias": jnp.tile(start, (L, 1))})
        # A ``relu2`` FFN has no gate matrix, dense, routed or shared.
        gated = c.ffn_act == "swiglu"
        if dense_ffn is None:     # a mixer alone: its one norm was set
            layers.pop("mlp_norm", None)
            return layers
        if dense_ffn:
            if gated:
                layers["w_gate"] = dense(next(k), (L, c.d_model, c.d_ff),
                                         c.d_model)
            layers.update({
                "w_up": dense(next(k), (L, c.d_model, c.d_ff), c.d_model),
                "w_down": dense(next(k), (L, c.d_ff, c.d_model), c.d_ff),
            })
            return layers
        E, H, F, l = c.n_experts, c.experts_here, c.expert_width, \
            c.expert_d_in
        layers["router"] = dense(next(k), (L, c.d_model, E), c.d_model)
        if gated:
            layers["moe_gate"] = dense(next(k), (L, H, l, F), l)
        layers.update({
            "moe_up": dense(next(k), (L, H, l, F), l),
            "moe_down": dense(next(k), (L, H, F, l), F),
        })
        if c.score_func == "sigmoid":
            # float32 whatever param_dtype: it is compared with scores.
            layers["expert_bias"] = jnp.zeros((L, E), jnp.float32)
        if c.n_shared_experts:
            Fs = c.shared_width
            if gated:
                layers["shared_gate"] = dense(next(x), (L, c.d_model, Fs),
                                              c.d_model)
            layers.update({
                "shared_up": dense(next(x), (L, c.d_model, Fs),
                                   c.d_model),
                "shared_down": dense(next(x), (L, Fs, c.d_model), Fs),
            })
            if c.shared_expert_gate:
                layers["shared_score"] = dense(next(x), (L, c.d_model, 1),
                                               c.d_model)
        if c.moe_latent:
            layers.update({
                "moe_lat_down": dense(next(x), (L, c.d_model, l),
                                      c.d_model),
                "moe_lat_up": dense(next(x), (L, l, c.d_model), l),
            })
        return layers

    # The stacks this model has, each with its kind and depth. "layers"
    # draws from ``k`` ahead of the embedding and the head, as it always
    # did; every other stack from a fold of its own.
    folds = {"dense_layers": 2, "conv_layers": 3, "dense_conv_layers": 4,
             "linear_layers": 5, "dense_linear_layers": 6}

    def stacks_of(plan, key, main):
        """The stacks ``plan`` names, drawn from ``key``: the model's
        (``main``: its key deals the embedding and the head too) or the
        MTP module's."""
        stacks, made = {}, {}
        for spec in plan:
            stacks[spec.stack] = (spec.mixer, spec.dense_ffn, spec.index + 1)
        for at, name in enumerate(sorted(stacks,
                                         key=lambda n: n != "layers")):
            mixer, dense_ffn, L = stacks[name]
            if name == "layers" and main is not None:
                dealt = main, iter(jax.random.split(
                    jax.random.fold_in(key, 1), 8))
            else:
                # a mamba run's fold follows its place among the stacks
                lead = jax.random.split(jax.random.fold_in(
                    key, folds.get(name, 16 + at)), 16)
                dealt = iter(lead[:8]), iter(lead[8:])
            made[name] = stack(*dealt, L, mixer, dense_ffn)
        return made

    params = stacks_of(c.layer_plan(), key, k)
    params["embed"] = (jax.random.normal(
        next(k), (c.vocab_size, c.d_model), jnp.float32) * 0.02).astype(pd)
    params["final_norm"] = jnp.ones(c.d_model, pd)
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(k), (c.d_model, c.vocab_size),
                                  c.d_model)
    if c.loop_steps > 1:
        # The exits' gate, a Linear(d_model, 1) with a bias.
        params["exit_gate_w"] = dense(jax.random.fold_in(key, 65),
                                      (c.d_model,), c.d_model)
        params["exit_gate_b"] = jnp.zeros(1, pd)
    if c.mtp_layers:
        mkey = jax.random.fold_in(key, 64)
        params["mtp"] = {
            **stacks_of(c.layer_plan(mtp=True), mkey, None),
            "token_norm": jnp.ones(c.d_model, pd),
            "hidden_norm": jnp.ones(c.d_model, pd),
            # [embedding ; hidden] side by side -> d_model
            "eh_proj": dense(jax.random.fold_in(mkey, 1),
                             (2 * c.d_model, c.d_model), 2 * c.d_model),
            "final_norm": jnp.ones(c.d_model, pd),
        }
    return params


def _softplus_inverse(y):
    """``x`` with ``softplus(x) = y``, ``y`` > 0."""
    return y + jnp.log(-jnp.expm1(-y))


def llama_partition_rules(pipeline=False):
    """Megatron TP + FSDP sharding rules for the param pytree.

    Layer-stacked tensors have a leading layer axis — unsharded by
    default, split over the "pipe" mesh axis when ``pipeline`` is set
    (contiguous layer blocks = GPipe stages; see parallel.pipeline). The
    ``tensor`` axis splits heads / ffn; ``fsdp`` shards the other matmul
    dimension ZeRO-3 style. Pass to parallel.shard_params.
    """
    lead = "pipe" if pipeline else None
    return [
        (r"embed", P(("tensor", "fsdp"), None)),
        # attn_norm, mlp_norm and, where the config has them, q_norm and
        # k_norm: a gain vector per layer, replicated.
        (r"layers/.*norm", P(lead, None)),
        # "layers/" matches every stack of ``LlamaConfig.layer_plan``
        # ("dense_layers/", "conv_layers/", "sparse_layers/",
        # "lightning_layers/", ...): all shard alike; a lightning
        # layer's ``out_norm`` is a gain with the norms above, its
        # ``wg`` a projection with these.
        (r"layers/w[qkvg]$", P(lead, "fsdp", "tensor")),
        (r"layers/wo", P(lead, "tensor", "fsdp")),
        # Latent attention: the down-projections split like the stream,
        # the up-projections by heads.
        (r"layers/w(q|kv)_a", P(lead, "fsdp", None)),
        (r"layers/w(q|kv)_b", P(lead, None, "tensor")),
        # Hyper-connections: a projection a stream; 27 numbers a part.
        (r"layers/hc_\w+_phi", P(lead, None, "fsdp", None)),
        (r"layers/hc_\w+_(alpha|bias)", P(lead, None)),
        # The short convolution: projections like attention's, the taps
        # (one weight a channel and tap) replicated.
        (r"layers/conv_in", P(lead, "fsdp", "tensor")),
        (r"layers/conv_out", P(lead, "tensor", "fsdp")),
        (r"layers/conv_w", P(lead, None, None)),
        # Gated DeltaNet: data and fsdp only (the mixer refuses a tensor
        # or sequence axis); the taps and the per-head gates replicated.
        (r"layers/gdn_in", P(lead, "fsdp", None)),
        (r"layers/gdn_out$", P(lead, None, "fsdp")),
        (r"layers/gdn_(ba|conv)", P(lead, None, None)),
        (r"layers/gdn_(a_log|dt_bias)", P(lead, None)),
        # Mamba: as Gated DeltaNet (the mixer refuses a tensor or
        # sequence axis); what is one number a channel, tap or state
        # replicated.
        (r"layers/ssm_in", P(lead, "fsdp", None)),
        (r"layers/ssm_out", P(lead, None, "fsdp")),
        (r"layers/ssm_(x|dt|conv|a_log)$", P(lead, None, None)),
        (r"layers/ssm_(dt_bias|conv_bias|d)$", P(lead, None)),
        # Mamba-2: as Mamba.
        (r"layers/ssd_in", P(lead, "fsdp", None)),
        (r"layers/ssd_out$", P(lead, None, "fsdp")),
        (r"layers/ssd_conv$", P(lead, None, None)),
        (r"layers/ssd_(dt_bias|conv_bias|a_log|d)$", P(lead, None)),
        # The latent projections round the routed experts.
        (r"layers/moe_lat_down", P(lead, "fsdp", None)),
        (r"layers/moe_lat_up", P(lead, None, "fsdp")),
        (r"mtp/eh_proj", P("fsdp", None)),
        (r"mtp/\w+_norm", P(None)),
        (r"layers/(w|shared)_(gate|up)", P(lead, "fsdp", "tensor")),
        (r"layers/(w|shared)_down", P(lead, "tensor", "fsdp")),
        # MoE: experts shard over the "expert" mesh axis (EP); within an
        # expert the FFN shards like the dense MLP. The router is tiny and
        # stays replicated.
        (r"layers/(router|shared_score)", P(lead, None, None)),
        (r"layers/expert_bias", P(lead, None)),
        (r"layers/moe_(gate|up)", P(lead, "expert", "fsdp", "tensor")),
        (r"layers/moe_down", P(lead, "expert", "tensor", "fsdp")),
        (r"final_norm", P(None)),
        (r"exit_gate", P(None)),
        (r"lm_head", P("fsdp", "tensor")),
    ]


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


_rmsnorm = scope("hvd.norm")(_rms)


def _input_norm(x, lp, name, c):
    """A part's input: the stream under the part's norm ``lp[name]``,
    or (``post_norm`` "only": no such leaf) as it stands."""
    if c.post_norm == "only":
        return x
    return _rmsnorm(x, lp[name].astype(c.compute_dtype), c.norm_eps)


@scope("hvd.attn.rope")
def _rope(x, positions, theta, rotary=0, freqs=None, mult=1.0):
    """Rotary embedding (half-split) of ``x`` [B, T, H, D]; positions
    are GLOBAL indices [B, T] so sequence sharding stays correct.
    ``rotary``: the leading dimensions of a head that turn (their own
    two halves paired, frequencies over ``rotary``), the rest pass as
    they are; 0 = the whole head. ``freqs`` [D / 2]: the frequencies
    where they are not ``theta``'s own, cos and sin times ``mult``
    (``LlamaConfig.yarn``)."""
    rest = None
    if rotary and rotary < x.shape[-1]:
        x, rest = x[..., :rotary], x[..., rotary:]
    b, t, h, d = x.shape
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32)
                          / (d // 2))
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,T,d/2]
    def table(f):
        t = f(angles)[:, :, None, :]
        return (t * mult if mult != 1.0 else t).astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = jnp.split(x, 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return turned if rest is None else jnp.concatenate([turned, rest], -1)


def _over_sequence(mesh, seq_axis):
    """True where ``mesh`` splits the sequence: attention then runs on a
    ring or Ulysses, and the flash kernels nowhere."""
    return bool(mesh is not None and seq_axis
                and mesh.shape.get(seq_axis, 1) > 1)


def _kernel_mesh(mesh):
    """The mesh a Mosaic call shards itself over (GSPMD cannot partition
    one): ``mesh``, except under pipelining, where the layer body
    already runs inside the pipeline's own shard_map."""
    if mesh is not None and mesh.shape.get("pipe", 1) > 1:
        return None
    return mesh


@scope("hvd.attn.core")
def _attention(q, k, v, mesh, seq_axis, seq_parallel="ring",
               flash_block=0, window=0, head_major=False):
    """``q`` [B, T, H, D], ``k``, ``v`` [B, T, Hkv, D] -> [B, T, H, D];
    ``head_major``: the operands arrive as the flash kernels take them,
    [B, H, T, D] (``ops/qk_prep.py``; never over a sequence axis)."""
    # remat="attn" naming: the SP paths name their OUTPUT ("attn_out");
    # the flash path names its custom-VJP residuals internally
    # (flash_o/flash_lse) instead — naming the transposed output TOO
    # would save a ~671 MB duplicate of flash_o at bench shapes (the
    # transpose is a distinct buffer) for no backward work saved.
    if _over_sequence(mesh, seq_axis):
        if window:
            raise ValueError("sliding-window layers run on the flash "
                             "kernel only: no sequence-parallel mesh "
                             "axis (ring / ulysses know no window)")
        if seq_parallel == "ulysses":
            from horovod_tpu.parallel.ulysses import ulysses_self_attention

            return checkpoint_name(
                ulysses_self_attention(q, k, v, mesh, causal=True,
                                       batch_axis=("data", "fsdp"),
                                       seq_axis=seq_axis), "attn_out")
        if seq_parallel not in ("ring", None):
            raise ValueError(f"unknown seq_parallel {seq_parallel!r}: "
                             "expected 'ring' or 'ulysses'")
        return checkpoint_name(
            ring_self_attention(q, k, v, mesh, causal=True,
                                batch_axis=("data", "fsdp"),
                                seq_axis=seq_axis), "attn_out")
    # Pallas flash kernel on TPU (no T^2 score materialization, so the
    # layer no longer needs full remat for memory). flash_attention
    # owns the remat naming for both of its paths: the pallas kernels
    # name their VJP residuals (flash_o/flash_lse), the off-TPU
    # fallback names its output attn_out.
    from horovod_tpu.ops.flash_attention import (
        flash_attention, flash_attention_head_major)

    blocks = {"block_q": flash_block, "block_k": flash_block} \
        if flash_block else {}
    if window:
        blocks["window"] = window
    return (flash_attention_head_major if head_major else flash_attention)(
        q, k, v, causal=True, mesh=_kernel_mesh(mesh), **blocks)


def _activation_spec(mesh):
    """[B, T, D] activations: batch over data+fsdp, seq over seq axis."""
    return P(("data", "fsdp"), "seq", None)


def _flat_proj(h, w, gain, c):
    """``h [..., D] @ w`` as the matmul leaves it, ``[..., heads *
    head_dim]``, behind the RMSNorm over the whole projected width where
    the configuration has that one (``qk_norm`` True)."""
    y = h @ w.astype(c.compute_dtype)
    if gain is not None and c.qk_norm != "head":
        y = _rmsnorm(y, gain.astype(c.compute_dtype), c.norm_eps)
    return y


def _head_proj(h, w, gain, c):
    """``h [..., D] @ w`` split into heads ``[..., heads, head_dim]``;
    with a ``gain`` (``qk_norm``) the RMSNorm over the whole projected
    width comes first, or (``qk_norm="head"``) over each head after.
    The ONE q/k/v projection of training, prefill and cached decode
    (models/generate.py)."""
    y = _flat_proj(h, w, gain, c)
    y = y.reshape(*y.shape[:-1], -1, c.head_dim)
    if gain is not None and c.qk_norm == "head":
        # Over each head's own width, one gain shared by the heads.
        y = _rmsnorm(y, gain.astype(c.compute_dtype), c.norm_eps)
    return y


@scope("hvd.attn.proj")
def _project_qkv(h, lp, c):
    """Normalized ``h`` -> (q, k, v) in heads, BEFORE RoPE."""
    return (_head_proj(h, lp["wq"], lp["q_norm"] if c.qk_norm else None, c),
            _head_proj(h, lp["wk"], lp["k_norm"] if c.qk_norm else None, c),
            _head_proj(h, lp["wv"], None, c))


def _prepared_qkv(h, lp, c, positions, rope, mesh):
    """``_project_qkv`` and (where the layer has ``rope``) ``_rope`` for
    the flash kernels, HEAD-MAJOR: q [B, H, T, d], k, v [B, Hkv, T, d]. The
    projections stay as the matmuls leave them and ``ops/qk_prep.py``'s
    kernel pair does the rest in one pass (``qk_prep.on_kernels`` says
    where)."""
    from horovod_tpu.ops.qk_prep import qk_prep

    dt = c.compute_dtype
    with scope("hvd.attn.proj"):
        flat = [_flat_proj(h, lp[w], lp[g] if g and c.qk_norm else None, c)
                for w, g in (("wq", "q_norm"), ("wk", "k_norm"),
                             ("wv", None))]
    gains = [lp[g].astype(dt) if c.qk_norm == "head" else None
             for g in ("q_norm", "k_norm")]
    return qk_prep(*flat, *gains, positions, c.rope_theta if rope else None,
                   c.head_dim, c.norm_eps, _kernel_mesh(mesh))


def _one_key_for_all_heads(k_r, heads):
    """Latent attention's rotated key ``k_r`` [B, T, 1, dr] as every
    head reads it: the SAME ``dr`` values. The expressions' form, off the
    TPU; on the chip ``ops/mla_prep.py``'s forward kernel stores the one
    rotated ``k_r`` behind every head's ``k_n``, and its backward sums
    ``dk``'s rotated slices over the heads."""
    return jnp.broadcast_to(k_r, (*k_r.shape[:2], heads, k_r.shape[3]))


def _latent_attention(h, lp, c, positions, mesh, seq_axis):
    """Multi-head latent attention on normalized ``h`` [B, T, D] -> what
    the layer adds (DeepSeek-V2, section 2.1, the training form):
    ``c_q = RMSNorm(h W_qa)``, ``[q_n | q_r] = c_q W_qb`` a head;
    ``[c_kv | k_r] = h W_kva``, ``c_kv`` under its RMSNorm, ``[k_n | v]
    = c_kv W_kvb`` a head; ``q = [q_n | RoPE(q_r)]``, ``k = [k_n |
    RoPE(k_r)]`` with ONE ``k_r`` for all heads
    (``LlamaConfig.yarn``: frequencies and scale); causal softmax
    attention at the handed-in scale with queries and keys
    ``qk_head_dim`` wide beside values ``v_head_dim`` wide (the flash
    kernels on the chip); ``W_o``. The two latents and ``k_r`` carry the
    names ``mla_c_q``, ``mla_c_kv``, ``mla_k_r``: what the "attn" remat
    modes save of the projections (1,344 values a token at Xing4's
    sizes, where ``q``, ``k``, ``v`` are 16,384), so that the backward
    pass re-runs the up-projections and not the down-projections.
    Scopes: the five matmuls and the two latent norms ``hvd.mla.proj``;
    the rotation, the assembly of ``q`` and ``k`` and the attention
    ``hvd.mla.core``: on the chip ONE kernel pair, ``hvd_mla_prep_fwd`` /
    ``_bwd``, takes the up-projections' outputs as the matmuls leave
    them and writes ``q``, ``k``, ``v`` head-major for the flash kernels
    (``ops/mla_prep.py``; ``mla_prep.on_kernels`` says where). Elsewhere
    the expressions below run, the rotation under ``hvd.attn.rope``."""
    from horovod_tpu.ops import mla_prep
    from horovod_tpu.ops.flash_attention import (
        flash_attention, flash_attention_head_major)

    if _over_sequence(mesh, seq_axis):
        raise ValueError(
            "latent attention runs on no sequence-parallel mesh axis "
            "yet: ring and ulysses take one width for q, k and v")
    dt = c.compute_dtype
    b, t, _ = h.shape
    H, dn, rkv = c.n_heads, c.qk_nope_head_dim, c.kv_lora_rank
    freqs, mult, scale = c.yarn()
    seam = mla_prep.on_kernels(h, H, dn, c.qk_rope_head_dim, c.v_head_dim)
    with scope("hvd.mla.proj"):
        c_q = _rms(h @ lp["wq_a"].astype(dt), lp["q_a_norm"].astype(dt),
                   c.norm_eps)
        c_kv, k_r = jnp.split(h @ lp["wkv_a"].astype(dt), [rkv], axis=-1)
        c_kv = _rms(c_kv, lp["kv_a_norm"].astype(dt), c.norm_eps)
        c_q = checkpoint_name(c_q, "mla_c_q")
        c_kv = checkpoint_name(c_kv, "mla_c_kv")
        k_r = checkpoint_name(k_r, "mla_k_r")
        q = c_q @ lp["wq_b"].astype(dt)
        kv = c_kv @ lp["wkv_b"].astype(dt)
        if not seam:
            q = q.reshape(b, t, H, c.qk_head_dim)
            kv = kv.reshape(b, t, H, dn + c.v_head_dim)
    attend = {"causal": True, "mesh": _kernel_mesh(mesh), "scale": scale}
    if c.flash_block:
        attend.update(block_q=c.flash_block, block_k=c.flash_block)
    if seam:
        with scope("hvd.mla.core"):
            o = flash_attention_head_major(
                *mla_prep.mla_prep(q, kv, k_r, positions, freqs, mult, dn,
                                   attend["mesh"]), **attend)
    else:
        turn = partial(_rope, positions=positions, theta=None,
                       freqs=jnp.asarray(freqs), mult=mult)
        q_r, k_r = turn(q[..., dn:]), turn(k_r[:, :, None, :])
        with scope("hvd.mla.core"):
            q = jnp.concatenate([q[..., :dn], q_r], -1)
            k = jnp.concatenate([kv[..., :dn],
                                 _one_key_for_all_heads(k_r, H)], -1)
            o = flash_attention(q, k, kv[..., dn:], **attend)
    with scope("hvd.mla.proj"):
        return o.reshape(b, t, -1) @ lp["wo"].astype(dt)


# ``H_post = _HC_POST_SCALE * sigmoid(.)``: a part's output may be added
# to a stream up to twice over (mHC, arXiv 2512.24880).
_HC_POST_SCALE = 2.0


def _sinkhorn(logits, iters, eps, clamp):
    """Float32 ``logits`` [n, n, tokens] (row, column, token: the tokens
    on the lanes, where [tokens, n, n] would fill a 128-lane tile with
    four values) -> the matrices ``exp(clamp(logits))`` after ``iters``
    Sinkhorn-Knopp iterations: rows over (their sum + ``eps``), then
    columns over (theirs + ``eps``). Plain arithmetic under a
    ``lax.scan``, so the gradient passes through every iteration; a
    loop, not ``iters`` copies of the body, on THIS path, the
    expression's: unrolled into the XLA program they made the grad
    program's executable 28% larger for a step 6% shorter (PR 57). On
    the chip the iterations run inside ``ops/hc_mix.py``'s kernels, on a
    tile of tokens in VMEM, as a loop there too (a kernel's body is
    traced and lowered in every process, so a copy of the body is paid
    in set-up, and the chain of divisions gains nothing from lying
    flat): the trade is closed for the chip (PERF.md section 6,
    PR 58)."""
    def iteration(m, _):
        m = m / (m.sum(1, keepdims=True) + eps)
        return m / (m.sum(0, keepdims=True) + eps), None

    return lax.scan(iteration, jnp.exp(jnp.clip(logits, *clamp)), None,
                    length=iters)[0]


def _hc_sizes(c):
    """What ``ops/hc_mix.py``'s kernels take of the arithmetic, read as
    the program is traced (``_HC_POST_SCALE`` among them: a program a
    value)."""
    from horovod_tpu.ops.hc_mix import Sizes

    return Sizes(int(c.hc_sinkhorn_iters), float(c.hc_eps),
                 tuple(float(x) for x in c.hc_clamp), float(c.norm_eps),
                 float(_HC_POST_SCALE))


def _hc_coefficients(X, phi, alpha, bias, c, sharded=False):
    """The three sets of coefficients of one part from the streams ``X``
    [B, n, T, D] -> float32 (``H_pre`` [B, n, T], ``H_post`` [B, n, T],
    ``H_res`` [B, n, n, T]: row, column). ``x~ Phi`` is ``(x Phi) / rms``:
    the streams multiply ``phi`` [n, D, n (n + 2)] as they are stored
    (the compute dtype's operands, float32 accumulation, a stream a
    matmul), the RMS over all ``n D`` values (no gain) divides the 24
    results; everything from there on is float32. One algorithm, two
    carriers: where ``hc_mix.on_kernels`` says so (streams on a TPU,
    whole lane slabs and tiles, a carry no mesh axis divides:
    ``sharded``) what comes back is what ``hvd_hc_pre_fwd`` computed,
    the program's own coefficients; elsewhere this expression, which is
    also what the kernels are held to."""
    from horovod_tpu.ops import hc_mix

    if hc_mix.on_kernels(X, sharded):
        return hc_mix.coefficients(X, phi, alpha, bias, _hc_sizes(c))
    f32, n = jnp.float32, c.hc_mult
    b, _, t, d = X.shape
    proj = sum(jnp.matmul(X[:, i], phi[i].astype(X.dtype),
                          preferred_element_type=f32) for i in range(n))
    square = jnp.square(X.astype(f32)).sum((1, 3)) / (n * d)     # [B, T]
    proj = proj * lax.rsqrt(square + c.norm_eps)[..., None]
    # tokens last from here: [B, 24, T]
    proj = jnp.swapaxes(proj, 1, 2)
    scale = alpha.astype(f32)[np.repeat(np.arange(3), [n, n, n * n])]
    raw = proj * scale[:, None] + bias.astype(f32)[:, None]
    pre, post, res = jnp.split(raw, [n, 2 * n], axis=1)
    res = jnp.moveaxis(res.reshape(b, n, n, t), 0, 2)     # [n, n, B, T]
    res = _sinkhorn(res.reshape(n, n, b * t), c.hc_sinkhorn_iters,
                    c.hc_eps, c.hc_clamp).reshape(n, n, b, t)
    return (jax.nn.sigmoid(pre), _HC_POST_SCALE * jax.nn.sigmoid(post),
            jnp.moveaxis(res, 2, 0))


def _hyper_connection(X, lp, name, c, part, sharded=False):
    """One part round the streams ``X`` [B, n, T, D] (mHC, arXiv
    2512.24880): ``u = sum_i H_pre[i] X[i]`` is what ``part`` reads
    ([B, T, D] -> (its output, its aux)), and ``X'[i] = sum_j H_res[i,
    j] X[j] + H_post[i] part(u)``; the coefficients from ``lp``'s
    ``hc_<name>_*`` leaves (``_hc_coefficients``). The streams are read
    and written in the compute dtype, the sums over streams run in
    float32; elementwise passes a stream, never a matmul of 4 x 4.

    One algorithm, two carriers, chosen by what the code observes
    (``hc_mix.on_kernels``: the streams on a TPU, ``D`` whole lane
    slabs, the tokens whole tiles, and no mesh axis dividing the carry,
    ``sharded``): on the chip two Pallas kernel pairs in the carry's own
    layout, ``hvd_hc_pre_fwd`` / ``_bwd`` ahead of the part and
    ``hvd_hc_post_fwd`` / ``_bwd`` behind it (``ops/hc_mix.py``: one
    read of ``X`` each, the iterations in VMEM, ``dX`` rounded once, no
    float32 copy of the carry in HBM); elsewhere the expression below,
    which is also the kernels' reference in the tests."""
    from horovod_tpu.ops import hc_mix

    leaves = (lp[f"hc_{name}_phi"], lp[f"hc_{name}_alpha"],
              lp[f"hc_{name}_bias"])
    if hc_mix.on_kernels(X, sharded):
        return hc_mix.hyper_connection(X, *leaves, part, _hc_sizes(c))
    f32, n = jnp.float32, c.hc_mult
    with scope("hvd.hc.mix"):
        pre, post, res = _hc_coefficients(X, *leaves, c, sharded)
        Xf = X.astype(f32)
        u = (pre[..., None] * Xf).sum(1).astype(X.dtype)
    y, aux = part(u)
    with scope("hvd.hc.mix"):
        mixed = sum(res[:, :, j, :, None] * Xf[:, j, None]
                    for j in range(n))
        out = mixed + post[..., None] * y.astype(f32)[:, None]
        return out.astype(X.dtype), aux


@scope("hvd.conv.chain")
def gated_short_conv(proj, w):
    """The chain between a conv layer's two projections: ``proj``
    [B, T, 3D] is ``[B, C, z]`` side by side, ``w`` [taps, D] one weight
    a channel and tap -> ``C * c`` [B, T, D] with ``c_t = sum_j w_j *
    (B * z)_{t - (taps-1) + j}``, zero before position 0 (depthwise and
    causal). Shifts, not ``lax.conv``: three multiply-adds a channel
    that the compiler fuses into one elementwise pass; in ``proj``'s
    dtype, the taps summed in float32."""
    gate_in, gate_out, z = jnp.split(proj, 3, axis=-1)
    return gate_out * _causal_taps(gate_in * z, w).astype(proj.dtype)


def _causal_taps(u, w, bias=None):
    """``c_t = sum_j w_j * u_{t - (taps-1) + j}`` of ``u`` [B, T, D]
    under the taps ``w`` [taps, D], zero before position 0: a depthwise
    causal convolution as shifted multiply-adds, summed in float32 (and
    returned so), plus a ``bias`` [D] where the layer has one. The one
    convolution of the conv, the linear_attention and the mamba
    mixers."""
    w = w.astype(jnp.float32)
    taps, t = w.shape[0], u.shape[1]
    conv = u.astype(jnp.float32) * w[taps - 1]
    for back in range(1, taps):      # u as it was ``back`` tokens ago
        past = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
        conv = conv + past.astype(jnp.float32) * w[taps - 1 - back]
    return conv if bias is None else conv + bias.astype(jnp.float32)


@scope("hvd.conv.proj")
def _short_conv(h, lp, c):
    """lfm2's gated short convolution, the token mixer of a ``conv``
    layer, on normalized ``h`` [B, T, D]: ``W_out (C * conv(B * z))``
    with ``[B, C, z] = split3(W_in h)`` (:func:`gated_short_conv`)."""
    dt = c.compute_dtype
    return gated_short_conv(h @ lp["conv_in"].astype(dt), lp["conv_w"]) \
        @ lp["conv_out"].astype(dt)


def _gdn_chain_in(qkvz, taps, hk, hv, dk, dv):
    """The chain's first stage as an expression: ``qkvz`` [B, T, 2 hk dk
    + 2 hv dv] = ``[q, k, v, z]`` side by side -> the convolution and
    SiLU over ``[q, k, v]``, ``q`` and ``k`` unit vectors a key head in
    float32 (``q`` times ``dk^-1/2``), everything by heads. What runs
    off the TPU, and what ``ops/gdn_chain.py:chain_in`` is held to."""
    from horovod_tpu.ops.gdn_chain import UNIT_EPS

    dt, f32 = qkvz.dtype, jnp.float32
    b, t, _ = qkvz.shape
    u, z = jnp.split(qkvz, [2 * hk * dk + hv * dv], axis=-1)
    u = jax.nn.silu(_causal_taps(u, taps).astype(dt))
    q, k, v = jnp.split(u, [hk * dk, 2 * hk * dk], axis=-1)

    def unit(x):     # [B, T, hk*dk] -> unit vectors a key head
        x = x.reshape(b, t, hk, dk).astype(f32)
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + UNIT_EPS)

    return ((unit(q) * dk ** -0.5).astype(dt), unit(k).astype(dt),
            v.reshape(b, t, hv, dv), z.reshape(b, t, hv, dv))


def _gdn_chain_out(o, z, gain, eps):
    """The chain's second stage as an expression: ``RMSNorm(o) * gain *
    SiLU(z)`` a value head, ``o`` and ``z`` [B, T, hv, dv], statistics
    in float32 (``ops/gdn_chain.py:chain_out`` on the TPU)."""
    dt, f32 = o.dtype, jnp.float32
    o = o.astype(f32)
    o = (o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
         ).astype(dt) * gain
    return o * jax.nn.silu(z.astype(f32)).astype(dt)


def _gated_delta_net(x, lp, c, mesh, seq_axis, stage=lambda f: f):
    """qwen3_next's Gated DeltaNet, the token mixer of a
    ``linear_attention`` layer, on the residual stream ``x`` [B, T, D]
    -> what it adds. Two stages. Before the rule: the layer's norm;
    ``[q, k, v, z] = h W_in``, ``[b, a] = h W_ba``; a depthwise causal
    convolution of ``conv_taps`` taps and SiLU over ``[q, k, v]``; ``q``
    and ``k`` L2-normalised a head, ``q`` scaled by ``dk^-1/2``; ``beta
    = sigmoid(b)`` and ``g = -exp(A_log) * softplus(a + dt_bias)`` a
    value head and token, in float32. The rule and what follows it: the
    gated delta rule (``ops/gated_delta_rule.py``) on ``q``, ``k`` [B, T,
    hk dk] and ``v`` [B, T, hv dv] as the first stage leaves them, heads
    side by side, a key head serving ``hv / hk`` value heads by its
    index (nothing is reshaped or repeated between the chain and the
    rule); ``RMSNorm(o) * SiLU(z)`` a head of ``o`` [B, T, hv dv] as the
    rule leaves it; the output projection. ``stage`` wraps each (remat
    "attn/ffn": a checkpoint of its own, so that the first's residuals
    and the rule's are never alive together). The chain round the rule runs as
    ``ops/gdn_chain.py``'s kernel pairs where the operands live on a TPU
    (keys and values of one width, or of two whose columns fall into
    steps of whole lane groups: ``gdn_chain.on_kernels``), as
    ``_gdn_chain_in`` / ``_gdn_chain_out`` elsewhere. ``post_norm``
    "only" leaves the layer's norm out (the stream enters as it is);
    ``linear_beta_max`` scales ``beta``."""
    from horovod_tpu.ops import gdn_chain
    from horovod_tpu.ops.gated_delta_rule import gated_delta_rule

    if mesh is not None and (
            (seq_axis and mesh.shape.get(seq_axis, 1) > 1)
            or mesh.shape.get("tensor", 1) > 1):
        raise ValueError(
            "a linear_attention layer runs whole on each device of the "
            "data and fsdp axes: its state passes from token to token "
            "(no sequence axis) and a key head's state serves "
            "value_heads / key_heads value heads (no tensor axis yet)")
    dt, f32 = c.compute_dtype, jnp.float32
    b, t, _ = x.shape
    hk, hv = c.linear_key_heads, c.linear_value_heads
    dk, dv = c.linear_key_dim, c.linear_value_dim
    kernels = gdn_chain.on_kernels(x, dk, dv, hk, hv)

    def before(x, lp):
        h = _input_norm(x, lp, "gdn_norm", c)
        with scope("hvd.gdn.proj"):
            qkvz = h @ lp["gdn_in"].astype(dt)
            ba = h @ lp["gdn_ba"].astype(dt)
        with scope("hvd.gdn.chain"):
            if kernels:     # heads side by side, z too: chain_out's
                q, k, v, z = gdn_chain.chain_in(qkvz, lp["gdn_conv"], hk, hv,
                                                dk, dv)
            else:
                q, k, v, z = _gdn_chain_in(qkvz, lp["gdn_conv"], hk, hv,
                                           dk, dv)
                q, k, v = (a.reshape(b, t, -1) for a in (q, k, v))
            bb, aa = jnp.split(ba.astype(f32), 2, axis=-1)
            g = -jnp.exp(lp["gdn_a_log"].astype(f32)) * jax.nn.softplus(
                aa + lp["gdn_dt_bias"].astype(f32))
            beta = jax.nn.sigmoid(bb)
            if c.linear_beta_max != 1.0:
                beta = c.linear_beta_max * beta
            return q, k, v, z, g, beta

    def rule_and_after(q, k, v, z, g, beta, lp):
        with scope("hvd.gdn.core"):
            o = gated_delta_rule(q, k, v, g, beta, key_heads=hk)
        with scope("hvd.gdn.chain"):
            gain = lp["gdn_out_norm"].astype(dt)
            if kernels:
                o = gdn_chain.chain_out(o, z, gain, c.norm_eps)
            else:
                o = _gdn_chain_out(o.reshape(b, t, hv, dv), z, gain,
                                   c.norm_eps).reshape(b, t, hv * dv)
        with scope("hvd.gdn.proj"):
            return o @ lp["gdn_out"].astype(dt)

    return stage(rule_and_after)(*stage(before)(x, lp), lp)


def _mamba(x, lp, c, mesh, seq_axis):
    """jamba's Mamba-1 mixer, the token mixer of a ``mamba`` layer, on
    the residual stream ``x`` [B, T, D] -> what it adds: the layer's
    norm; ``[u, z] = h W_in``; a depthwise causal convolution of
    ``conv_taps`` taps (with its bias) and SiLU over ``u``; ``[r, B, C]
    = u W_x``, each under an RMSNorm of its own; ``dt = softplus(r W_dt
    + b_dt)`` and ``A = -exp(A_log)`` in float32; the selective scan
    (``ops/selective_scan.py``); ``y * SiLU(z)``; the output
    projection. Scopes: the four matmuls ``hvd.ssm.proj``, the scan
    ``hvd.ssm.core``, everything elementwise between them
    ``hvd.ssm.chain`` (the layer's norm ``hvd.norm``)."""
    from horovod_tpu.ops.selective_scan import selective_scan

    if mesh is not None and (
            (seq_axis and mesh.shape.get(seq_axis, 1) > 1)
            or mesh.shape.get("tensor", 1) > 1):
        raise ValueError(
            "a mamba layer runs whole on each device of the data and "
            "fsdp axes: its state passes from token to token (no "
            "sequence axis) and its convolution, norms and scan see "
            "every channel (no tensor axis yet)")
    dt, f32 = c.compute_dtype, jnp.float32
    n, r = c.mamba_d_state, c.mamba_dt_rank
    h = _rmsnorm(x, lp["ssm_norm"].astype(dt), c.norm_eps)
    with scope("hvd.ssm.proj"):
        uz = h @ lp["ssm_in"].astype(dt)
    with scope("hvd.ssm.chain"):
        u, z = jnp.split(uz, 2, axis=-1)
        u = jax.nn.silu(_causal_taps(u, lp["ssm_conv"],
                                     lp.get("ssm_conv_bias"))).astype(dt)
    with scope("hvd.ssm.proj"):
        rbc = u @ lp["ssm_x"].astype(dt)
    with scope("hvd.ssm.chain"):
        rank, Bm, Cm = jnp.split(rbc, [r, r + n], axis=-1)
        rank, Bm, Cm = (
            _rms(a, lp[g].astype(dt), c.norm_eps) for a, g in (
                (rank, "ssm_dt_norm"), (Bm, "ssm_b_norm"),
                (Cm, "ssm_c_norm")))
    with scope("hvd.ssm.proj"):
        step = jnp.matmul(rank, lp["ssm_dt"].astype(dt),
                          preferred_element_type=f32)
    with scope("hvd.ssm.chain"):
        step = jax.nn.softplus(step + lp["ssm_dt_bias"].astype(f32))
        rates = -jnp.exp(lp["ssm_a_log"].astype(f32))
    with scope("hvd.ssm.core"):
        y = selective_scan(u, step, rates, Bm, Cm, lp["ssm_d"])
    with scope("hvd.ssm.chain"):
        y = y * jax.nn.silu(z.astype(f32)).astype(dt)
    with scope("hvd.ssm.proj"):
        return y @ lp["ssm_out"].astype(dt)


def _ssd_chain_in(zxr, taps, bias, di, gn):
    """The chain's first stage as an expression: ``zxr`` [B, T, 2 di + 2
    gn + H] = ``[z, X, B, C, r]`` side by side -> the convolution (its
    bias where there is one) and SiLU over ``[X, B, C]`` in float32,
    rounded once; ``z`` and ``r`` as they stand. What runs off the TPU,
    and what ``ops/ssd_chain.py:chain_in`` is held to."""
    z, xbc, r = jnp.split(zxr, [di, 2 * di + 2 * gn], axis=-1)
    xbc = jax.nn.silu(_causal_taps(xbc, taps, bias)).astype(zxr.dtype)
    X, Bm, Cm = jnp.split(xbc, [di, di + gn], axis=-1)
    return X, Bm, Cm, z, r


def _ssd_chain_out(y, z, gain, groups, eps):
    """The chain's second stage as an expression: the gate FIRST, ``y *
    SiLU(z)`` in float32, then an RMSNorm over each of ``groups`` runs
    of channels of ``y``, ``z`` [B, T, di], times the ``gain`` [di]
    (``ops/ssd_chain.py:chain_out`` on the TPU)."""
    dt, f32 = y.dtype, jnp.float32
    b, t, di = y.shape
    y = y.reshape(b, t, groups, di // groups).astype(f32) \
        * jax.nn.silu(z.astype(f32)).reshape(b, t, groups, di // groups)
    return (y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            ).astype(dt).reshape(b, t, di) * gain


def _mamba2(x, lp, c, mesh, seq_axis):
    """nemotron_h's Mamba-2 mixer, the token mixer of a ``mamba2`` layer,
    on the residual stream ``x`` [B, T, D] -> what it adds: the layer's
    norm; ``[z, xBC, r] = h W_in``; a depthwise causal convolution of
    ``conv_taps`` taps (with its bias) and SiLU over ``xBC`` = ``[X, B,
    C]``, ``B`` and ``C`` a group of heads; ``dt = softplus(r + b_dt)``
    and ``A = -exp(A_log)`` a head, in float32; the SSD recurrence
    (``ops/ssd.py``); the gate FIRST, ``y * SiLU(z)``, then an RMSNorm
    over each group's channels; the output projection. Scopes: the two
    matmuls ``hvd.ssd.proj``, the recurrence ``hvd.ssd.core``,
    everything elementwise between them ``hvd.ssd.chain`` (the layer's
    norm ``hvd.norm``). The chain's two stages run as
    ``ops/ssd_chain.py``'s kernel pairs (``hvd_ssd_chain_in_fwd`` /
    ``_in_bwd`` / ``_out_fwd`` / ``_out_bwd``, in the matmuls' ``[B, T,
    columns]``) where the operands live on a TPU and the columns are
    whole lane slabs, as ``_ssd_chain_in`` / ``_ssd_chain_out``
    elsewhere."""
    from horovod_tpu.ops import ssd_chain
    from horovod_tpu.ops.ssd import ssd

    if mesh is not None and (
            (seq_axis and mesh.shape.get(seq_axis, 1) > 1)
            or mesh.shape.get("tensor", 1) > 1):
        raise ValueError(
            "a mamba2 layer runs whole on each device of the data and "
            "fsdp axes: its state passes from token to token (no "
            "sequence axis) and its convolution, group norm and "
            "recurrence see every head (no tensor axis yet)")
    dt, f32 = c.compute_dtype, jnp.float32
    b, t, _ = x.shape
    H, G, N = c.ssd_heads, c.ssd_groups, c.ssd_state
    di, gn = c.ssd_d_inner, G * N
    stage_one, stage_two = (
        (ssd_chain.chain_in, ssd_chain.chain_out)
        if ssd_chain.on_kernels(x, di, G, gn)
        else (_ssd_chain_in, _ssd_chain_out))
    h = _rmsnorm(x, lp["ssd_norm"].astype(dt), c.norm_eps)
    with scope("hvd.ssd.proj"):
        zxr = h @ lp["ssd_in"].astype(dt)
    with scope("hvd.ssd.chain"):
        X, Bm, Cm, z, r = stage_one(zxr, lp["ssd_conv"],
                                    lp.get("ssd_conv_bias"), di, gn)
        step = jax.nn.softplus(r.astype(f32)
                               + lp["ssd_dt_bias"].astype(f32))
        rates = -jnp.exp(lp["ssd_a_log"].astype(f32))
    with scope("hvd.ssd.core"):
        y = ssd(X.reshape(b, t, H, -1), step, rates,
                Bm.reshape(b, t, G, N), Cm.reshape(b, t, G, N),
                lp["ssd_d"], c.ssd_chunk)
    with scope("hvd.ssd.chain"):
        y = stage_two(y.reshape(b, t, di), z,
                      lp["ssd_out_norm"].astype(dt), G, c.norm_eps)
    with scope("hvd.ssd.proj"):
        return y @ lp["ssd_out"].astype(dt)


def _lightning(x, lp, c, mesh, seq_axis):
    """minicpm_sala's ``lightning-attn`` mixer, the token mixer of a
    ``lightning_attention`` layer, on the residual stream ``x`` [B, T, D]
    -> what it adds: the layer's norm; ``q``, ``k``, ``v`` and the gate
    ``z`` projected, ``H`` heads of ``d`` each; ``q`` and ``k`` under an
    RMSNorm a head (one gain of ``d`` a projection) and RoPE over the
    whole head; from ``S_0 = 0`` a state a head, ``S_t = lambda S_{t-1}
    + k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)`` with no denominator,
    ``lambda = exp(rate)`` a constant of the layer and head
    (``lp["lightning_rate"]``, ``LlamaConfig.lightning_rates``: no leaf);
    an RMSNorm over the concatenated heads, the gate ``sigmoid(z)``; the
    output projection. The recurrence IS ``ops/ssd.py``'s with ``x = v``,
    ``B = k``, ``C = q / sqrt(d)``, ``dt = 1``, no ``D``, a group a head:
    it runs there. Scopes: the five matmuls ``hvd.attn.proj``, the
    recurrence ``hvd.lightning.core``, what stands between them
    ``hvd.lightning.chain`` (float32 inside, rounded once a side)."""
    from horovod_tpu.ops.ssd import ssd

    if mesh is not None and (
            (seq_axis and mesh.shape.get(seq_axis, 1) > 1)
            or mesh.shape.get("tensor", 1) > 1):
        raise ValueError(
            "a lightning_attention layer runs whole on each device of "
            "the data and fsdp axes: its state passes from token to "
            "token (no sequence axis) and its output norm sees every "
            "head (no tensor axis yet)")
    dt, f32 = c.compute_dtype, jnp.float32
    b, t, _ = x.shape
    H, d = c.lightning_heads, c.lightning_head_dim
    h = _rmsnorm(x, lp["attn_norm"].astype(dt), c.norm_eps)
    with scope("hvd.attn.proj"):
        q, k, v, z = (h @ lp[w].astype(dt) for w in ("wq", "wk", "wv", "wg"))
    with scope("hvd.lightning.chain"):
        freqs = c.rope_theta ** (-jnp.arange(0, d // 2, dtype=f32)
                                 / (d // 2))
        angles = jnp.arange(t, dtype=f32)[:, None] * freqs      # [T, d/2]
        cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

        def turned(y, gain, scale):
            """The norm a head, the rotation (half-split, as ``_rope``)
            and the scale in float32; each half rounded as it is made,
            so that no float32 [B, T, H d] array stands in HBM."""
            y = _rms(y.reshape(b, t, H, d).astype(f32), gain.astype(f32),
                     c.norm_eps)
            y1, y2 = jnp.split(y, 2, axis=-1)
            return jnp.concatenate(
                [((y1 * cos - y2 * sin) * scale).astype(dt),
                 ((y1 * sin + y2 * cos) * scale).astype(dt)], -1)

        q = turned(q, lp["q_norm"], d ** -0.5)
        k = turned(k, lp["k_norm"], 1.0)
    with scope("hvd.lightning.core"):
        y = ssd(v.reshape(b, t, H, d), jnp.ones((b, t, H), f32),
                lp["lightning_rate"], k, q, None, c.lightning_chunk,
                "hvd.lightning.core")
    with scope("hvd.lightning.chain"):
        y = (_rms(y.reshape(b, t, H * d).astype(f32),
                  lp["out_norm"].astype(f32), c.norm_eps)
             * jax.nn.sigmoid(z.astype(f32))).astype(dt)
    with scope("hvd.attn.proj"):
        return y @ lp["wo"].astype(dt)


def _sparse_core(q, k, v, c, mesh, seq_axis):
    """A ``sparse_attention`` layer's attention on ``q`` [B, T, H, d],
    ``k``, ``v`` [B, T, Hkv, d] past ``sparse_dense_len`` tokens: the
    selection (``hvd.sparse.select``; its table named ``sparse_sel`` for
    the remat policies, so that the backward reads the forward's choice
    and chooses nothing again) and the attention over what it chose
    (``hvd.sparse.core``), both ``ops/sparse_attention.py``'s."""
    from horovod_tpu.ops import sparse_attention as sa

    if mesh is not None and (
            _over_sequence(mesh, seq_axis)
            or mesh.shape.get("tensor", 1) > 1):
        raise ValueError(
            "a sparse_attention layer past its dense length runs whole "
            "on each device of the data and fsdp axes: a token's blocks "
            "are chosen over the whole sequence and by a group's heads "
            "together (no sequence or tensor axis yet)")
    with scope("hvd.sparse.select"):
        table = checkpoint_name(sa.select_blocks(
            q, k, block=c.sparse_block, topk=c.sparse_topk,
            kernel=c.sparse_kernel, stride=c.sparse_stride,
            init_blocks=c.sparse_init_blocks,
            window_blocks=c.sparse_window_blocks), "sparse_sel")
    with scope("hvd.sparse.core"):
        return sa.sparse_attention(q, k, v, table, c.sparse_block)


def _slot_holds(idx, n_experts):
    """``[..., K, E]`` bool: slot k of a token holds expert e. A pick by
    ``idx`` is a select on it under a sum, which XLA fuses into one
    reduction: nothing ``[..., K, E]`` long reaches HBM. A token's K
    indices are distinct, so every sum has at most ONE term that is not
    zero and is exact in float32, what a gather and a scatter of scalars
    give at 7-19 ns an element on the chip (PERF.md section 6, PR 54)."""
    return idx[..., None] == lax.iota(idx.dtype, n_experts)


def _unpick(idx, g, n_experts):
    """The transpose of :func:`_pick`: ``g [..., K]`` put back at
    ``idx`` in ``[..., E]``, zero where no slot chose."""
    return jnp.where(_slot_holds(idx, n_experts), g[..., None], 0).sum(-2)


@jax.custom_vjp
def _pick(probs, idx):
    """``probs [..., E]`` at ``idx [..., K]`` (distinct along K), equal
    to ``jnp.take_along_axis(probs, idx, -1)`` and its VJP's scatter bit
    for bit, as dense compare-and-select passes (:func:`_slot_holds`).
    The VJP keeps ``idx`` and nothing of ``probs``' size."""
    return jnp.where(_slot_holds(idx, probs.shape[-1]),
                     probs[..., None, :], 0).sum(-1)


def _pick_fwd(probs, idx):
    return _pick(probs, idx), (idx, jnp.zeros((0, probs.shape[-1]),
                                               probs.dtype))


def _pick_bwd(res, g):
    idx, like = res                  # ``like``: the width E, no data
    return _unpick(idx, g, like.shape[-1]), None


_pick.defvjp(_pick_fwd, _pick_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(probs, k):
    """``lax.top_k`` over the last axis whose VJP selects by the
    indices the FORWARD chose (:func:`_unpick`), named ``moe_gate_idx``:
    every remat mode
    that saves the sorted order (``_MOE_SAVE``) saves them with it.
    ``lax.top_k``'s own VJP reads the indices of a RECOMPUTED top-k. A
    recomputation that rounds two of a token's probabilities the other
    way then hands slot k's gate gradient to another expert, and counts
    other group sizes than the saved order was sorted by, so that every
    row between two moved boundaries meets the wrong expert's matrix in
    ``dlhs`` and ``tgmm``. Read on the chip once the layers ran unrolled
    (under the scan the recomputation happened to round as the forward
    did): the experts' gradients stood 0.04-0.15 and the router's
    0.07-0.20 from the float32 reference, 0.02 with the choice saved
    (PERF.md section 6, PR 33)."""
    return tuple(lax.top_k(probs, k))


def _top_k_fwd(probs, k):
    vals, idx = lax.top_k(probs, k)
    idx = checkpoint_name(idx, "moe_gate_idx")
    return (vals, idx), (idx, jnp.zeros((0, probs.shape[-1]), probs.dtype))


def _top_k_bwd(k, res, g):
    idx, like = res
    return (_unpick(idx, g[0], like.shape[-1]),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


@scope("hvd.moe.route")
def moe_route(h, router_w, n_experts_per_token, norm_topk_prob=True,
              score_func="softmax", bias=None, route_scale=1.0):
    """The ONE router: f32 logits matmul, softmax, top-K, the pick of
    the K chosen probabilities (dense: :func:`_pick`), renormalised
    (epsilon-guarded) where ``norm_topk_prob`` and as the softmax gave
    them where not, and the load-balancing statistics of these tokens.
    ``score_func="sigmoid"`` scores each
    expert by the sigmoid of its logit instead; a ``bias`` [E] (float32,
    data: no gradient) is added to the scores for the CHOICE of the K
    experts only, their weights are the scores themselves;
    ``route_scale`` multiplies the weights last (afmoe's ``route_norm``
    is ``norm_topk_prob``; its 1e-20 guard is the one here, which no
    sum of K sigmoids comes near). Shared by the GShard dispatch below,
    the dropless grouped dispatch (ops/grouped_moe.py), and cached
    decode (models/generate.py) so the three can never drift.

    ``h`` is [..., D] with any leading shape; returns (gate_vals
    [..., K] f32, gate_idx [..., K] int32, balance [2, E] f32): row 0
    the share of tokens that chose expert e, summed over all K choices
    (data: no gradient), row 1 the mean router probability of e.
    :func:`moe_balance_loss` turns them into the aux term.
    """
    E = router_w.shape[-1]
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if score_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)            # [..., E]
    if bias is None:
        gate_vals, gate_idx = _top_k(probs, n_experts_per_token)
    else:
        _, gate_idx = lax.top_k(
            probs + lax.stop_gradient(bias.astype(jnp.float32)),
            n_experts_per_token)
        gate_idx = checkpoint_name(gate_idx, "moe_gate_idx")
        gate_vals = _pick(probs, gate_idx)
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    if route_scale != 1.0:
        gate_vals = gate_vals * route_scale
    lead = tuple(range(probs.ndim - 1))
    chosen = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(-2)
    return gate_vals, gate_idx, jnp.stack([chosen.mean(lead),
                                           probs.mean(lead)])


def route_layer(h, lp, c):
    """:func:`moe_route` with one expert layer's parameters and the
    configuration's router fields: the one call every dispatch makes."""
    return moe_route(h, lp["router"], c.n_experts_per_token,
                     c.norm_topk_prob, c.score_func,
                     lp.get("expert_bias"), c.route_scale)


@scope("hvd.moe.route")
def moe_balance_loss(balance):
    """The load-balancing aux term from :func:`moe_route`'s statistics,
    pooled over every leading axis of ``balance [..., 2, E]``:
    ``E * sum_e <share of tokens that chose e> * <mean probability of
    e>``, all K choices counted (Hugging Face's
    ``load_balancing_loss_func``; K at perfectly uniform routing, E at
    total collapse). Layers see equally many tokens, so pooling the
    stacked per-layer statistics is pooling all layers' tokens, as the
    published loss does. A pipeline stage cannot see another stage's or
    microbatch's tokens and applies it per layer and microbatch. Dense
    layers carry zero-width statistics: 0."""
    pooled = balance.mean(tuple(range(balance.ndim - 2)))
    return balance.shape[-1] * jnp.sum(pooled[0] * pooled[1])


def _moe_ffn(h, lp, c, mesh):
    """Top-k routed expert FFN, GShard-style grouped einsum dispatch.

    Static shapes throughout (XLA requirement): each batch row is a
    dispatch GROUP (GShard's group axis — without it the one-hot
    dispatch tensors are O(S²) in the token count); within a group,
    tokens scatter into per-expert buffers of fixed capacity C via
    one-hot tensors, and over-capacity tokens fall through on the
    residual (combine weight zero). Groups ride the batch sharding
    (data/fsdp); the [G, E, C, D] expert buffers get an "expert" axis
    constraint so GSPMD inserts the token all-to-alls — the TPU analog
    of expert-parallel dispatch. Reference analog: none (Horovod has no
    MoE); design follows the GShard/Switch public formulation.
    Returns (out [B,T,D], moe_route's balance statistics).
    """
    B, T, D = h.shape
    E, K = c.n_experts, c.n_experts_per_token
    C = max(int(T * K * c.capacity_factor / E), 1)

    if c.n_experts_held or c.moe_latent or c.ffn_act != "swiglu":
        raise ValueError("a share of the experts (n_experts_held), "
                         "latent experts (moe_latent) and a relu2 FFN "
                         "run through the grouped dispatch only: "
                         "moe_impl='grouped'")
    gate_vals, gate_idx, aux = route_layer(h, lp, c)           # [B,T,K]

    # Position of each (token, slot) in its expert's per-group capacity
    # buffer, filling slot 0 for every token before slot 1 (priority to
    # the top-1 expert, as in GShard).
    dt = c.compute_dtype
    with scope("hvd.moe.dispatch"):
        dispatch = jnp.zeros((B, T, E, C), dt)
        combine = jnp.zeros((B, T, E, C), dt)
        counts = jnp.zeros((B, E), jnp.int32)
        for slot in range(K):
            oh = jax.nn.one_hot(gate_idx[..., slot], E,
                                dtype=jnp.int32)                # [B,T,E]
            pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]
            keep = (pos < C) & (oh > 0)
            pos_oh = jax.nn.one_hot(pos, C, dtype=dt) \
                * keep[..., None].astype(dt)                    # [B,T,E,C]
            dispatch = dispatch + pos_oh
            combine = combine + pos_oh * gate_vals[..., slot].astype(
                dt)[..., None, None]
            counts = counts + oh.sum(1)

    def constrain_e(z):
        if mesh is None:
            return z
        return lax.with_sharding_constraint(
            z, jax.sharding.NamedSharding(
                mesh, P(("data", "fsdp"), "expert", None, None)))

    # Named for remat="attn+gate" (the FFN-residual mode): the one-hot
    # cumsum routing chain above is bandwidth-bound vector work over
    # [B,T,E,C] tensors — saving its two products keeps backward from
    # re-running it (the MoE analog of the dense mode's saved gate).
    dispatch = checkpoint_name(dispatch, "moe_dispatch")
    combine = checkpoint_name(combine, "moe_combine")

    with scope("hvd.moe.dispatch"):
        xe = constrain_e(jnp.einsum("btec,btd->becd", dispatch,
                                    h.astype(dt)))            # [B,E,C,D]
    with scope("hvd.moe.experts"):
        gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xe,
                                      lp["moe_gate"].astype(dt)))
        up = jnp.einsum("becd,edf->becf", xe, lp["moe_up"].astype(dt))
        ye = constrain_e(jnp.einsum("becf,efd->becd", gate * up,
                                    lp["moe_down"].astype(dt)))
    with scope("hvd.moe.combine"):
        y = jnp.einsum("btec,becd->btd", combine, ye)         # [B,T,D]
    return y, aux


def _grouped_dispatch(c, mesh):
    """Whether this model's expert layers run the sorted grouped-GEMM
    dispatch (``ops/grouped_moe.py``) and not the GShard einsums."""
    return c.n_experts > 0 and (
        c.moe_impl == "grouped" or (c.moe_impl == "auto" and mesh is None))


@scope("hvd.ffn")
def _swiglu(h, gate, up, down, dt):
    return (jax.nn.silu(h @ gate.astype(dt)) * (h @ up.astype(dt))) \
        @ down.astype(dt)


@scope("hvd.ffn")
def _relu2(h, up, down, dt):
    """``ffn_act`` "relu2": two matrices, ``relu(h Wu)^2 Wd``."""
    return jnp.square(jax.nn.relu(h @ up.astype(dt))) @ down.astype(dt)


def _ffn(h, lp, c, mesh=None):
    """One layer's FFN on normalized activations: dense siglu MLP, or
    top-k expert routing (plus the shared expert, where the
    configuration has one) for a layer whose parameters hold a router.
    Returns (y, balance): the router's load-balancing statistics [2, E],
    zero-width for a dense layer (see moe_balance_loss).
    Shared by llama_forward and the cached decode path (generate.py) so
    the two can never diverge."""
    dt = c.compute_dtype
    if "router" in lp:
        if _grouped_dispatch(c, mesh):
            from horovod_tpu.ops.grouped_moe import grouped_moe_ffn

            rows = None
            if c.moe_latent:
                # The routed experts' rows in the latent space; the
                # router (and the shared expert) read ``h`` itself.
                with scope("hvd.moe.latent"):
                    rows = h @ lp["moe_lat_down"].astype(dt)
            y, aux = grouped_moe_ffn(h, lp, c, rows)
            if c.moe_latent:
                with scope("hvd.moe.latent"):
                    y = y @ lp["moe_lat_up"].astype(dt)
        elif c.moe_impl not in ("auto", "gshard"):
            raise ValueError(f"unknown moe_impl {c.moe_impl!r}: "
                             "expected 'auto', 'grouped', or 'gshard'")
        else:
            y, aux = _moe_ffn(h, lp, c, mesh)
        if c.n_shared_experts:
            if c.ffn_act == "relu2":
                shared = _relu2(h, lp["shared_up"], lp["shared_down"], dt)
            else:
                shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                                 lp["shared_down"], dt)
            if c.shared_expert_gate:
                with scope("hvd.ffn"):
                    shared = shared * jax.nn.sigmoid(
                        h @ lp["shared_score"].astype(dt))
            y = y + shared
        return y, aux
    # Named for remat="attn+ffn": saving the two up-projections (the
    # bulk of a layer's recomputed matmul FLOPs) lets backward rebuild
    # silu(gate)*up elementwise instead of re-running both matmuls.
    # The PRE-silu value is what must be saved — silu's own vjp needs
    # its primal input, so saving post-silu would still re-run the
    # matmul to regenerate it.
    if c.ffn_act == "relu2":
        return (_relu2(h, lp["w_up"], lp["w_down"], dt),
                jnp.zeros((2, 0), jnp.float32))
    if c.ffn_chunk and h.shape[0] * h.shape[1] > c.ffn_chunk:
        return _swiglu_in_blocks(h, lp, c), jnp.zeros((2, 0), jnp.float32)
    with scope("hvd.ffn"):
        gate_pre = checkpoint_name(h @ lp["w_gate"].astype(dt), "ffn_gate")
        up = checkpoint_name(h @ lp["w_up"].astype(dt), "ffn_up")
        y = (jax.nn.silu(gate_pre) * up) @ lp["w_down"].astype(dt)
    return y, jnp.zeros((2, 0), jnp.float32)


def _swiglu_in_blocks(h, lp, c):
    """The dense SwiGLU on ``h`` [B, T, D] in blocks of ``c.ffn_chunk``
    tokens (or the largest divisor of B*T under it), each under a
    checkpoint inside a ``lax.map``: forward and backward hold one
    block's three [block, d_ff] activations (1.07 GB each, whole, at
    32,768 tokens of 16,384), and a matrix's gradient is the sum of the
    blocks'. As ``_token_nll_in_blocks`` does for the head."""
    from horovod_tpu.ops.flash_attention import _pick_block

    dt = c.compute_dtype
    b, t, d = h.shape
    rows = _pick_block(b * t, c.ffn_chunk)
    # cast once: a block's cotangent is added to ONE accumulator a matrix
    w = tuple(lp[name].astype(dt) for name in ("w_gate", "w_up", "w_down"))
    y = lax.map(jax.checkpoint(lambda hb: _swiglu(hb, *w, dt)),
                h.reshape(b * t // rows, rows, d))
    return y.reshape(b, t, d)


def llama_forward(params, tokens, config, mesh=None, seq_axis="seq",
                  return_aux=False):
    """tokens [B, T] int32 -> logits [B, T, vocab] (float32).

    Under jit with a mesh, activations get sharding constraints so GSPMD
    lays out batch over data/fsdp and sequence over seq; the attention op
    switches to ring attention when seq parallelism is active. With
    ``return_aux`` the MoE load-balancing loss (moe_balance_loss over
    all layers' tokens; 0 for dense configs) is returned alongside the
    logits.
    """
    x, aux = _llama_hidden(params, tokens, config, mesh, seq_axis)
    logits = _head(params, x, config)
    if return_aux:
        return logits, aux
    return logits


def _head(params, x, c):
    """The final norm's output ``x`` [..., D] -> float32 logits [...,
    vocab]: bf16 operands, f32 accumulation (full MXU rate without
    giving up the f32 logits downstream softmax stability needs)."""
    dt = c.compute_dtype
    with scope("hvd.head"):
        if c.logit_div != 1.0:
            x = x * jnp.asarray(1.0 / c.logit_div, x.dtype)
        if c.tie_embeddings:
            # The embedding matrix [vocab, D] contracted over D where it
            # lies: no transposed copy; its gradient is the sum of this
            # use and the lookup's.
            return jnp.einsum("...d,vd->...v", x,
                              params["embed"].astype(dt),
                              preferred_element_type=jnp.float32)
        return jnp.matmul(x, params["lm_head"].astype(dt),
                          preferred_element_type=jnp.float32)


def _llama_hidden(params, tokens, config, mesh=None, seq_axis="seq"):
    """tokens [B, T] -> (what the head reads, [B, T, D] after the final
    norm; the MoE load-balancing loss): ``llama_forward`` less the
    head, which ``llama_loss`` may run in blocks of tokens."""
    if config.loop_steps > 1:   # the last trip's exit; no expert layers
        return _llama_exits(params, tokens, config, mesh, seq_axis)[-1], \
            jnp.zeros((), jnp.float32)
    x, aux = _llama_stream(params, tokens, config, mesh, seq_axis)
    return _final_norm(params, x, config), aux


def _final_norm(params, x, c):
    return _rmsnorm(x, params["final_norm"].astype(c.compute_dtype),
                    c.norm_eps)


def _llama_stream(params, tokens, config, mesh=None, seq_axis="seq"):
    """tokens [B, T] -> (the residual stream after the last layer, BEFORE
    the final norm; the MoE load-balancing loss)."""
    c = config
    b, t = tokens.shape
    x = _embedded(params, tokens, c, mesh, seq_axis)

    n_stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
    if n_stages > 1:
        # GPipe over the "pipe" axis: each stage scans its contiguous
        # layer block; microbatches rotate stage-to-stage via ppermute
        # (parallel.pipeline.gpipe). llama_forward always uses gpipe —
        # it must produce LOGITS, which the 1F1B schedule (loss fused
        # into the last stage; see llama_loss) never materializes.
        from horovod_tpu.parallel.pipeline import gpipe

        M = _validate_pipeline(c, b, mesh, seq_axis, n_stages)
        xs = x.reshape(M, b // M, t, x.shape[-1])
        ys, aux_total = gpipe(
            _stage_scan(_build_layer_body(c, mesh, seq_axis)),
            params["layers"], xs, mesh)
        x = ys.reshape(b, t, x.shape[-1])
        aux = aux_total / (c.n_layers * M)
    else:
        x, balance = _run_layers(params, x, c, mesh, seq_axis)
        aux = moe_balance_loss(balance)

    return x, aux


def _embedded(params, tokens, c, mesh, seq_axis):
    """tokens [B, T] -> their embeddings [B, T, D] in the activation
    layout: what the first layer reads."""
    if _over_sequence(mesh, seq_axis):
        whole = [f for f in ("one_part_layers", "mtp_layers")
                 if getattr(c, f)]
        if whole:
            raise ValueError(
                f"LlamaConfig fields {whole} run on no sequence-parallel "
                "mesh axis (ring / ulysses) yet: a one-part layer's "
                "recurrence and the MTP term's shift by a token see a "
                "whole sequence")

    # Layout contract for the vocab lookup: tokens are pinned to the
    # activation layout (batch over data/fsdp, seq over seq) so the SPMD
    # partitioner picks INDEX-passthrough for the gather — each device
    # all-gathers the (small) table shard and gathers its own token
    # block, and the output is born in the activation layout. Without the
    # pin it picks operand-passthrough (output sharded over the table's d
    # axis) and then "involuntary full rematerialization" to reshard
    # [B,T,D] into the batch/seq layout.
    if mesh is not None:
        tokens = lax.with_sharding_constraint(
            tokens, jax.sharding.NamedSharding(mesh, P(("data", "fsdp"),
                                                       "seq")))
    return _constrain(_embed(params, tokens, c), mesh)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _for_each_trip(shared, trips):
    """The leaves every trip shares, once a trip. Forward: the same
    arrays ``trips`` times. Backward: a shared leaf's gradient is the
    SUM of its visits', taken here in float32 and rounded once, under
    ``hvd.loop`` (left to autodiff it is an ``add_any`` in the leaves'
    dtype under no scope of ours)."""
    return (shared,) * trips


def _for_each_trip_fwd(shared, trips):
    return (shared,) * trips, None


def _for_each_trip_bwd(trips, _, visits):
    with scope("hvd.loop"):
        return (jax.tree.map(
            lambda *g: sum(x.astype(jnp.float32) for x in g).astype(
                g[0].dtype), *visits),)


_for_each_trip.defvjp(_for_each_trip_fwd, _for_each_trip_bwd)


def _llama_exits(params, tokens, c, mesh, seq_axis):
    """tokens [B, T] -> what the ``loop_steps`` exits read, [R, B, T,
    D]: a trip is the stack of layers and then the final norm, on the
    weights every trip shares; its output is what that trip's exit reads
    AND what the next trip starts from (the first: the embeddings).

    The trips are a Python loop, each a ``_run_layers`` of its own (for
    a uniform dense stack ONE ``lax.scan`` of the layer body): the
    backward pass then holds one stacked gradient a trip and
    ``_for_each_trip`` sums them in one pass. As a ``lax.scan`` over
    trips with the stack closed over, the transposed scan adds a whole
    stacked gradient a trip in the leaves' dtype and copies every
    visit's saved activations into and out of a buffer a trip: 12 layers
    of 2048 x 5632 at 2 x 4096 tokens, four trips, remat ``attn``, on
    one v5e chip 1,306.9 ms a step and 6.84 GB of temporaries against
    1,272.6 ms and 5.40 GB for this form (PERF.md section 6, PR 64)."""
    n_stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
    if n_stages > 1:    # refuses ``loop_steps`` by name
        _validate_pipeline(c, tokens.shape[0], mesh, seq_axis, n_stages)
    shared = {k: params[k] for k in {spec.stack for spec in c.layer_plan()}
              | {"final_norm"}}
    x = _embedded(params, tokens, c, mesh, seq_axis)
    exits = []
    for mine in _for_each_trip(shared, c.loop_steps):
        x, _ = _run_layers(mine, x, c, mesh, seq_axis)
        with scope("hvd.loop"):
            x = _constrain(_rms(
                x, mine["final_norm"].astype(c.compute_dtype), c.norm_eps),
                mesh)
        exits.append(x)
    return jnp.stack(exits)


def _exit_log_probs(s):
    """The exits' gate logits ``s`` [R, ...] float32 -> the log of the
    exit distribution [R, ...]: ``log p_t = log sigmoid(s_t) + sum_{j<t}
    log sigmoid(-s_j)``, the last exit's what is left, ``sum_{j<R} log
    sigmoid(-s_j)``."""
    stays = jnp.cumsum(jax.nn.log_sigmoid(-s[:-1]), 0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(s[:1]),
         jax.nn.log_sigmoid(s[1:-1]) + stays[:-1], stays[-1:]], 0)


def _exit_terms(params, exits, batch, c):
    """What the looped decoder's loss is made of, a token and exit, from
    what the exits read, ``exits`` [R, B, T, D]: the cross-entropy of
    each exit's logits through the ONE head, [R, B, T], and the log of
    the exit distribution, [R, B, T], in float32 from the gates
    ``lambda_t = sigmoid(h_t . w + b)``: ``p_t = lambda_t prod_{j<t} (1
    - lambda_j)``, the last exit taking what is left (``lambda_R``
    enters nothing).

    The head reads the R exits as R x B sequences: one pass of
    ``_head_nll``, whose blocks (``loss_chunk``) add the head's gradient
    into one accumulator."""
    R, b, t, d = exits.shape
    nll = _head_nll(params, exits.reshape(R * b, t, d),
                    jnp.tile(batch["targets"], (R, 1)), c).reshape(R, b, t)
    with scope("hvd.exit"):
        return nll, _exit_log_probs(
            jnp.einsum("rbtd,d->rbt", exits,
                       params["exit_gate_w"].astype(c.compute_dtype),
                       preferred_element_type=jnp.float32)
            + params["exit_gate_b"].astype(jnp.float32))


def _masked_mean(x, mask):
    """The mean of ``x`` [..., B, T] over the tokens ``mask`` [B, T]
    keeps (None: all)."""
    if mask is None:
        return jnp.mean(x, (-2, -1))
    mask = mask.astype(jnp.float32)
    return jnp.sum(x * mask, (-2, -1)) / jnp.maximum(jnp.sum(mask), 1.0)


def _exit_loss(params, exits, batch, c):
    """The looped decoder's loss (Ouro's stage-I objective, joint:
    nothing is detached): a token's R cross-entropies in expectation
    under its exit distribution, less ``exit_entropy_weight`` times that
    distribution's entropy (:func:`_exit_terms`); the (masked) mean over
    tokens."""
    nll, log_p = _exit_terms(params, exits, batch, c)
    with scope("hvd.exit"):
        per_token = jnp.sum(jnp.exp(log_p) * (
            nll + c.exit_entropy_weight * log_p), 0)
    with scope("hvd.loss"):
        return _masked_mean(per_token, batch.get("mask"))


def llama_exit_terms(params, batch, config, mesh=None, seq_axis="seq"):
    """What a looped decoder's ``llama_loss`` is made of on ``batch``,
    each the (masked) mean over tokens: (the cross-entropy of every exit
    [R], the exit distribution [R], its entropy). The loss is ``sum(p x
    cross-entropy)`` a TOKEN before the mean, so these do not add up to
    it; they are what a training run watches beside it."""
    nll, log_p = _exit_terms(params, _llama_exits(
        params, batch["tokens"], config, mesh, seq_axis), batch, config)
    p, mask = jnp.exp(log_p), batch.get("mask")
    return (_masked_mean(nll, mask), _masked_mean(p, mask),
            _masked_mean(-jnp.sum(p * log_p, 0), mask))


def llama_expert_load(params, tokens, config):
    """How the router spread ``tokens`` [B, T] over the experts:
    [expert layers, n_experts] float32, the tokens that chose expert
    e at each layer, all K choices counted (a row sums to B*T*K: the
    grouped dispatch computes every one of them). From the layers' own
    ``moe_route`` statistics, single program, no mesh."""
    _, balance = _run_layers(params, _embed(params, tokens, config),
                             config, None, None)
    return balance[:, 0] * tokens.size


@scope("hvd.embed")
def _embed(params, tokens, c):
    x = params["embed"].astype(c.compute_dtype)[tokens]
    if c.embed_mult or c.scale_embed:
        x = x * jnp.asarray(c.embed_mult or c.d_model ** 0.5, x.dtype)
    return x


def _run_layers(params, x, c, mesh, seq_axis, mtp=False):
    """The decoder stack on ``x`` [B, T, D] (``mtp``: the MTP module's
    layers, ``params`` its own); returns (x, the expert layers' balance
    statistics stacked [layers, 2, E]).

    One algorithm, L layer bodies over stacked parameters, whose
    indexing is dynamic where the compiler can fuse it and static where
    it cannot:

    - a uniform DENSE stack (or GShard experts: einsums) is ONE
      ``lax.scan`` of the layer body: XLA fuses the scan's
      ``dynamic-slice`` into the matmul that reads the weight and its
      ``dynamic-update-slice`` into the one that writes the gradient, so
      the rolled loop costs nothing and the program is O(1) in depth;
    - a uniform stack of GROUPED expert layers (``_grouped_dispatch``)
      runs unrolled, each body on a STATIC index of ``params["layers"]``.
      Its matrices feed megablox custom calls, which no fusion enters:
      under a scan each layer's three expert matrices were copied out of
      the stack and their gradients copied back in, and the saved
      gate/up activations stacked and unstacked: 20.5 ms of a 169.1 ms
      step at OLMoE's widths, two layers (PERF.md section 6, PR 33);
    - a layer pattern (leading dense layers; window and full attention
      layers mixed; conv layers beside attention layers) is a different
      PROGRAM a kind, so it runs unrolled too, each layer the body of
      its own kind on its own entry of its own stack
      (``LlamaConfig.layer_plan``); but where a whole stack of dense
      layers of one kind stands in a row (a run of mamba layers, which
      is a stack of its own), that run is a ``lax.scan`` again.

    Unrolled, program size and compile time are O(depth). A pipeline
    stage (``_stage_scan``) always scans: one layer program by contract."""
    if c.hc_mult:
        # Hyper-connections: ``hc_mult`` copies in, their sum out.
        streams = jnp.broadcast_to(x[:, None], (x.shape[0], c.hc_mult,
                                                *x.shape[1:]))
        streams, balance = _run_layer_plan(params, _constrain(streams, mesh),
                                           c, mesh, seq_axis, mtp)
        with scope("hvd.hc.mix"):
            return streams.astype(jnp.float32).sum(1).astype(x.dtype), \
                balance
    return _run_layer_plan(params, x, c, mesh, seq_axis, mtp)


def _run_layer_plan(params, x, c, mesh, seq_axis, mtp):
    """``_run_layers`` on the stream as the layers carry it ([B, T, D],
    or [B, n, T, D] under hyper-connections)."""
    plan = c.layer_plan(mtp)
    kinds = {spec.kind for spec in plan}

    def stack_of(spec):
        """The stack's leaves and, beside them, what its layers read
        that is no leaf: a lightning layer's decay rates, a row a
        layer."""
        if spec.mixer != "lightning":
            return params[spec.stack]
        return {**params[spec.stack],
                "lightning_rate": jnp.asarray(c.lightning_rates(spec.stack))}

    if len(kinds) == 1 and not _grouped_dispatch(c, mesh):
        return lax.scan(_build_layer_body(c, mesh, seq_axis), x,
                        stack_of(plan[0]))
    from horovod_tpu.ops.grouped_moe import LayerOfStack

    bodies = {kind: _build_layer_body(c, mesh, seq_axis, kind=kind)
              for kind in kinds}
    # A SHARE of the experts (``n_experts_held``) gets slices: its later
    # chunks are a loop whose backward carries a gradient accumulator the
    # shape of each operand it is handed (``grouped_moe._later_chunks``;
    # a transposed ``lax.scan`` before it), and whole stacks there raised
    # the Trinity-Mini cell's grad program from 9.64 to 14.03 GB
    # (compiled for the described v5e, PR 33).
    whole = _EXPERT_MATRICES \
        if _grouped_dispatch(c, mesh) and not c.n_experts_held else ()
    depth = collections.Counter(spec.stack for spec in plan)
    balance, at = [], 0
    while at < len(plan):
        spec = plan[at]
        if spec.index == 0 and depth[spec.stack] > 1 \
                and not _grouped_dispatch(c, mesh) \
                and all(s.stack == spec.stack and s.kind == spec.kind
                        for s in plan[at:at + depth[spec.stack]]):
            # A WHOLE stack of one kind in a row (a run of mamba layers)
            # is the scan above inside the pattern: O(1) program a run,
            # and each layer's gradient written where it belongs. The
            # same layers unrolled leave every layer's gradient alive to
            # the program's end, where they are concatenated into the
            # stack's: 3.2 GB of a 1.6 B-parameter model's 4.56 GB of
            # temporaries (compiled for the described v5e, PR 47).
            x, bal = lax.scan(bodies[spec.kind], x, stack_of(spec))
            balance.extend(bal[i] for i in range(depth[spec.stack]))
            at += depth[spec.stack]
            continue
        lp = {k: LayerOfStack(w, spec.index) if k in whole
              else w[spec.index] for k, w in stack_of(spec).items()}
        x, bal = bodies[spec.kind](x, lp)
        balance.append(bal)
        at += 1
    # Dense layers carry zero-width statistics: the expert layers' only.
    return x, jnp.stack([b for b in balance if b.shape[-1]] or balance)


def _constrain(x, mesh):
    if mesh is None:
        return x
    spec = _activation_spec(mesh)
    if x.ndim == 4:     # [B, n, T, D]: the streams of hyper-connections
        spec = P(spec[0], None, *spec[1:])
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _stage_scan(body):
    """One pipeline stage = a scan of ``body`` over its layer block
    (shared by the gpipe and 1f1b paths); its aux is the sum of the
    block's per-layer balance losses on this microbatch."""
    def stage_fn(lp_stage, x_mb):
        x_out, balance = lax.scan(body, x_mb, lp_stage)
        return x_out, jnp.sum(jax.vmap(moe_balance_loss)(balance))
    return stage_fn


def _validate_pipeline(c, b, mesh, seq_axis, n_stages):
    """Shared gpipe/1f1b precondition checks; returns the microbatch
    count M. seq parallelism is mutually exclusive with pipelining in
    this layout (ring attention's own shard_map cannot nest inside the
    pipeline's)."""
    M = c.pipeline_microbatches or n_stages
    plan = c.layer_plan()
    unscheduled = [f for f in ("one_part_layers", "ffn_act", "moe_latent",
                               "shared_d_ff", "mtp_layers", "kv_lora_rank",
                               "hc_mult", "loop_steps")
                   if f in c.training_only_fields()]
    if unscheduled:
        raise ValueError(
            f"LlamaConfig fields {unscheduled} have no pipeline schedule "
            "yet: a stage scans ONE layer program of a mixer AND a "
            "SwiGLU FFN over params['layers'], the last stage's loss "
            "has one term, and what crosses a stage boundary is ONE "
            "stream [B, T, D] (hyper-connections carry hc_mult; latent "
            "attention's leaves have no stage layout), ONCE (a looped "
            "stack wants a circular schedule: a micro-batch round every "
            "stage loop_steps times)")
    if c.post_norm == "only" or c.linear_beta_max != 1.0:
        raise ValueError(
            "post_norm 'only' and linear_beta_max have no pipeline "
            "schedule yet: a stage's partition of params['layers'] and "
            "its schedules were written and are tested for layers that "
            "norm each part's INPUT (attn_norm and mlp_norm a stage's "
            "leaves; output-only norms have neither), and no stage "
            "holds the linear_attention mixer whose write strength "
            "linear_beta_max scales")
    if len({spec.kind for spec in plan}) > 1 \
            or plan[0].mixer != "attention" or c.tie_embeddings \
            or c.partial_rotary or c.shared_expert_gate:
        raise ValueError("a layer pattern (leading dense layers, window "
                         "and full attention mixed), conv, "
                         "linear_attention and mamba layers, a partial "
                         "RoPE, a "
                         "gated shared expert and a tied head have no "
                         "pipeline schedule yet: a "
                         "stage scans ONE attention layer program of "
                         "params['layers'], the last stage reads lm_head")
    if seq_axis and mesh.shape.get(seq_axis, 1) > 1:
        raise ValueError("pipeline (pipe>1) and sequence parallelism "
                         "(seq>1) cannot combine: ring attention's "
                         "shard_map cannot nest inside the pipeline's")
    if M <= 0 or b % M:
        raise ValueError(f"batch {b} must divide into "
                         f"{M} pipeline microbatches")
    V = c.pipeline_virtual_stages
    if V < 1:
        raise ValueError(f"pipeline_virtual_stages must be >= 1, got {V}")
    if V > 1 and c.pipeline_schedule != "interleaved_1f1b":
        raise ValueError(
            f"pipeline_virtual_stages={V} requires "
            f"pipeline_schedule='interleaved_1f1b' "
            f"(got {c.pipeline_schedule!r})")
    chunks = n_stages * (V if c.pipeline_schedule == "interleaved_1f1b"
                         else 1)
    if c.n_layers % chunks:
        raise ValueError(f"n_layers {c.n_layers} must divide into "
                         f"{chunks} pipeline stage chunks "
                         f"({n_stages} stages x {V} virtual)")
    return M


def _build_layer_body(c, mesh, seq_axis, constrain_acts=True, kind=None):
    """One decoder layer as a scan body, wrapped in the configured
    remat policy — shared by llama_forward (single-device and gpipe)
    and the 1F1B training path. ``constrain_acts=False`` drops the
    per-activation sharding constraints (the 1F1B path differentiates
    INSIDE the pipe-manual shard_map, and XLA CPU aborts transposing
    with_sharding_constraint on auto axes there; GSPMD still lays out
    activations by propagation from the sharded params). ``kind``:
    this layer's ``(mixer, dense_ffn, window, rope)`` of
    ``LlamaConfig.layer_plan`` (default: the first layer's, the only
    one a uniform model has); the FFN follows the parameters it is
    handed (``_ffn``)."""
    dt = c.compute_dtype
    mixer, dense_ffn, window, rope = kind or c.layer_plan()[0].kind
    # A layer of ONE part (``one_part_layers``): no mixer, or no FFN.
    two_parts = mixer is not None and dense_ffn is not None

    def constrain(x):
        return _constrain(x, mesh) if constrain_acts else x

    def joins(y):
        """What a part adds to the stream, times ``residual_mult``."""
        if c.residual_mult != 1.0:
            y = y * jnp.asarray(c.residual_mult, y.dtype)
        return constrain(y)

    def layer(x, lp):
        if mixer is None:
            return ffn(x, None, lp)
        if dense_ffn is None:
            return x + joins(mix(x, lp)), jnp.zeros((2, 0), jnp.float32)
        return ffn(x, mix(x, lp), lp)

    def mix(x, lp, stage=lambda f: f):
        """The token mixer on the stream (its norm first) -> what it
        adds. ``stage``: ``_gated_delta_net``'s."""
        if mixer == "conv":
            h = _rmsnorm(x, lp["conv_norm"].astype(dt), c.norm_eps)
            return _short_conv(h, lp, c)
        if mixer == "linear":
            return _gated_delta_net(x, lp, c, mesh, seq_axis, stage)
        if mixer == "mamba":
            return _mamba(x, lp, c, mesh, seq_axis)
        if mixer == "mamba2":
            return _mamba2(x, lp, c, mesh, seq_axis)
        if mixer == "lightning":
            return _lightning(x, lp, c, mesh, seq_axis)
        # Shapes from x, not the enclosing scope: under pipelining the
        # layer sees microbatches smaller than the full batch.
        bb, tt = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(tt), (bb, tt))
        h = _input_norm(x, lp, "attn_norm", c)
        if c.kv_lora_rank:    # its rotated slice is its own, whatever rope
            return _latent_attention(h, lp, c, positions, mesh, seq_axis)
        # One pass on the chip (ops/qk_prep.py), the expressions
        # elsewhere: which, is read off the input.
        from horovod_tpu.ops import qk_prep

        # A sparse layer past its dense length attends by tiles of
        # neighbouring tokens: its kernels take the heads as the
        # projections leave them, a token's side by side.
        sparse = mixer == "sparse" and tt > c.sparse_dense_len
        head_major = not sparse and qk_prep.on_kernels(
            x, c.head_dim, c.qk_norm == "head",
            (c.partial_rotary or c.head_dim) if rope else 0,
            _over_sequence(mesh, seq_axis))
        if head_major:
            q, kk, vv = _prepared_qkv(h, lp, c, positions, rope, mesh)
        else:
            q, kk, vv = _project_qkv(h, lp, c)
            if rope:
                q = _rope(q, positions, c.rope_theta, c.partial_rotary)
                kk = _rope(kk, positions, c.rope_theta, c.partial_rotary)
        # Named for remat="attn+gate+qkv": saving the POST-rope q/k and
        # v ([B,T,H(kv),D] bf16 — ~67 MB/layer at bench shapes) lets
        # backward skip the wq/wk/wv matmul + rope re-runs entirely
        # (attn_out/flash_o already cover wo's operands).
        q = checkpoint_name(q, "rope_q")
        kk = checkpoint_name(kk, "rope_k")
        vv = checkpoint_name(vv, "attn_v")
        # remat="attn" save-names applied inside _attention (per path).
        if sparse:
            attn = _sparse_core(q, kk, vv, c, mesh, seq_axis)
        else:
            attn = _attention(q, kk, vv, mesh, seq_axis, c.seq_parallel,
                              c.flash_block, window, head_major)
        with scope("hvd.attn.proj"):
            attn = attn.reshape(bb, tt, -1)
            if c.attn_gate:
                attn = attn * jax.nn.sigmoid(h @ lp["wg"].astype(dt))
            attn = attn @ lp["wo"].astype(dt)
        return attn

    def ffn(x, mixed, lp):
        """The mixer's output (None: the layer has no mixer) joins the
        stream; then the FFN's."""
        if mixed is not None:
            if c.post_norm:
                mixed = _rmsnorm(mixed, lp["post_attn_norm"].astype(dt),
                                 c.norm_eps)
            x = x + joins(mixed)

        h = _input_norm(x, lp, "mlp_norm", c)
        ff, aux = _ffn(h, lp, c, mesh)
        if c.post_norm:
            ff = _rmsnorm(ff, lp["post_mlp_norm"].astype(dt), c.norm_eps)
        x = x + joins(ff)
        return x, aux

    saved = ("attn_out", "flash_o", "flash_lse", "sparse_sel", "mla_c_q",
             "mla_c_kv", "mla_k_r")
    if c.hc_mult:
        # The same two parts round ``hc_mult`` streams [B, n, T, D]:
        # each reads ``_hyper_connection``'s blend and is added where
        # its coefficients say. A mesh axis that divides the carry
        # (``_activation_spec``: the batch, the sequence) keeps it on
        # the expression (GSPMD cannot partition a Mosaic call).
        carry_sharded = mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in ("data", "fsdp", "seq"))
        def hc_mix(x, lp):
            def part(u):
                y = mix(u, lp)
                if c.post_norm:
                    y = _rmsnorm(y, lp["post_attn_norm"].astype(dt),
                                 c.norm_eps)
                return joins(y), None
            return _hyper_connection(x, lp, "attn", c, part,
                                     carry_sharded)[0]

        def hc_ffn(x, lp):
            def part(u):
                ff, aux = _ffn(_rmsnorm(u, lp["mlp_norm"].astype(dt),
                                        c.norm_eps), lp, c, mesh)
                if c.post_norm:
                    ff = _rmsnorm(ff, lp["post_mlp_norm"].astype(dt),
                                  c.norm_eps)
                return joins(ff), aux
            x, aux = _hyper_connection(x, lp, "mlp", c, part, carry_sharded)
            return constrain(x), aux

        def streams_layer(x, lp):
            if mixer is None:
                return hc_ffn(x, lp)
            x = constrain(hc_mix(x, lp))
            if dense_ffn is None:
                return x, jnp.zeros((2, 0), jnp.float32)
            return hc_ffn(x, lp)

        layer = streams_layer

    body = layer
    if c.remat == "dots":
        body = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.dots_saveable)
    elif c.remat == "attn" or (c.remat == "attn/ffn" and not two_parts):
        # (A layer of one part under "attn/ffn": its one part under its
        # one checkpoint.)
        # Full remat except the attention output and the flash kernel's
        # residuals (o + logsumexp — one [B,T,H*D] bf16 and one
        # [B,H,1,T] f32 per layer): saving flash_lse is what actually
        # stops backward from re-running the flash forward — the
        # custom-vjp residuals are distinct from the outer attn_out
        # var, so naming only attn_out still recomputed the kernel
        # (profiled r3: ~12% of the step).
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
    elif c.remat == "attn/ffn":
        # "attn" with the mixer and the FFN each under a checkpoint of
        # its own (the mixer's output, [B,T,D], is saved between them),
        # and a linear_attention mixer under two (what the rule reads,
        # q, k, v, z and the gates, saved between them): the backward
        # pass recomputes and differentiates the FFN, drops its
        # residuals, and only then recomputes the mixer, stage by stage.
        # A checkpoint's recomputation waits for its cotangent, so the
        # stages' residuals are never alive together; under one
        # checkpoint a layer they all are. Three linear_attention
        # layers and one attention layer beside a share of the experts
        # at 2 x 8192 tokens, the grad program's temporaries compiled
        # for the described v5e (PR 38): "attn" 11.35 GiB, the mixer
        # under ONE checkpoint 9.00, this 7.74. No FLOP more.
        once = partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.save_only_these_names(
                           *saved))
        if c.hc_mult:   # a part WITH its stream mixing under each
            mix_once, ffn_once = once(hc_mix), once(hc_ffn)

            def body(x, lp):
                return ffn_once(constrain(mix_once(x, lp)), lp)
        else:
            mix_once = partial(mix, stage=once) if mixer == "linear" \
                else once(mix)
            ffn_once = once(ffn)

            def body(x, lp):
                return ffn_once(x, mix_once(x, lp), lp)
    elif c.remat in ("attn+moe", "moe") and not _grouped_dispatch(c, mesh):
        # These modes save residuals only grouped_moe_ffn emits; under
        # GShard dispatch (mesh present or moe_impl="gshard") or a
        # dense config they would silently degrade to plain "attn".
        raise ValueError(
            f"remat={c.remat!r} requires the grouped MoE dispatch "
            "(n_experts > 0 and moe_impl='grouped', or 'auto' with no "
            "mesh); use remat='attn' or 'attn+gate' here")
    elif c.remat == "attn+moe":
        # "attn" plus the grouped-MoE routing: the top-k choice
        # (S*K int32), the sorted order with its inverse (2 x S*K
        # int32) and the gate weights in that order, so backward
        # chooses and sorts nothing; it gathers the rows into expert
        # order again (saving them costs three passes over [S*K, D]:
        # jax's reduce_precision on a residual no fusion produces) and
        # re-runs the gate and up grouped GEMMs. (No gradient needs the
        # down projection's output: the gate weights scale its input
        # rows.)
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "flash_o", "flash_lse", *_MOE_SAVE))
    elif c.remat == "moe":
        # Also save the pre-silu gate and the up projection ([S*K, 2F]
        # bf16 per layer): backward re-runs NO grouped matmul. The HBM
        # price usually needs microbatched steps (gradient
        # accumulation) at real sizes; see
        # parallel.make_split_train_step's ``microbatches``.
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "flash_o", "flash_lse", *_MOE_SAVE,
                *_MOE_EXTRA_SAVE))
    elif c.remat == "attn+gate+qkv":
        # "attn+gate" plus the post-rope q/k/v: backward re-runs only
        # the rmsnorms and elementwise chains — no qkv matmuls, no
        # rope, no FFN gate matmul. The extra ~[B,T,2D] bf16 per layer
        # is the cheapest matmul-recompute elimination left after
        # attn+gate — FOR SHAPES WITH HBM HEADROOM: at the 16G-chip
        # flagship bench shape it exceeded HBM in r5, so the mode is
        # pinned by the CPU remat-equivalence test but has no on-chip
        # flagship measurement.
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "flash_o", "flash_lse", "ffn_gate",
                "moe_dispatch", "moe_combine", "rope_q", "rope_k",
                "attn_v"))
    elif c.remat in ("attn+ffn", "attn+gate"):
        # "attn" plus FFN up-projection residuals (pre-silu gate, and
        # for "attn+ffn" also up — [B,T,d_ff] each per layer): trades
        # d·d_ff matmul re-runs per layer for HBM — the largest
        # recompute term after attention. Measured on one v5e chip the
        # HBM price exceeds the win (the batch must shrink to fit, see
        # docs/benchmarks.md r4 notes); the modes exist for multi-chip
        # FSDP runs where per-chip activation memory is the constraint
        # that actually relaxes.
        names = ["attn_out", "flash_o", "flash_lse", "ffn_gate",
                 "moe_dispatch", "moe_combine"]
        if c.remat == "attn+ffn":
            names.append("ffn_up")
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(*names))
    elif c.remat in (False, "none"):
        pass
    elif c.remat in (True, "full"):
        body = jax.checkpoint(layer)
    else:
        raise ValueError(f"unknown remat mode {c.remat!r}: expected "
                         "True/'full', 'dots', 'attn', 'attn/ffn', "
                         "'attn+gate', 'attn+gate+qkv', 'attn+ffn', "
                         "'attn+moe', 'moe', or False/'none'")

    return body


def llama_loss(params, batch, config, mesh=None, seq_axis="seq"):
    """Causal LM loss (+ weighted MoE aux loss for expert configs).
    batch = {"tokens": [B,T], "targets": [B,T], "mask": [B,T] or absent}.

    With an active "pipe" mesh axis and ``pipeline_schedule="1f1b"``
    the loss runs through the interleaved 1F1B schedule (loss fused
    into the last stage, O(S) activation stash — see
    parallel.pipeline.one_f_one_b) instead of gpipe + a global logits
    pass; values and gradients are pinned equal by
    tests/single/test_pipeline_1f1b.py.
    """
    n_stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
    if n_stages > 1 and config.pipeline_schedule in ("1f1b",
                                                     "interleaved_1f1b"):
        return _llama_loss_1f1b(params, batch, config, mesh, seq_axis,
                                n_stages)
    if config.pipeline_schedule not in ("gpipe", "1f1b",
                                        "interleaved_1f1b"):
        raise ValueError(
            f"unknown pipeline_schedule {config.pipeline_schedule!r}: "
            "expected 'gpipe', '1f1b', or 'interleaved_1f1b'")
    if config.loop_steps > 1:
        return _exit_loss(params, _llama_exits(
            params, batch["tokens"], config, mesh, seq_axis), batch, config)
    stream, aux = _llama_stream(params, batch["tokens"], config, mesh,
                                seq_axis)
    nll = _head_nll(params, _final_norm(params, stream, config),
                    batch["targets"], config)
    mask = batch.get("mask")
    with scope("hvd.loss"):
        if mask is None:
            loss = jnp.mean(nll)
        else:
            mask = mask.astype(jnp.float32)
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if config.n_experts > 0 and config.moe_aux_weight:
        loss = loss + config.moe_aux_weight * aux
    if config.mtp_layers:
        loss = loss + config.mtp_weight * _mtp_loss(
            params, stream, batch, config, mesh, seq_axis)
    return loss


def _head_nll(params, x, targets, c):
    """The head on the final norm's output ``x`` and the cross-entropy a
    token, whole or (``loss_chunk``) in blocks of tokens."""
    if c.loss_chunk:
        return _token_nll_in_blocks(params, x, targets, c)
    return _token_nll(_head(params, x, c), targets)


def _mtp_hidden(params, stream, targets, c, mesh, seq_axis):
    """What the head reads of the MTP module, [B, T, D] after the
    module's own final norm: position ``t`` of the main model's residual
    stream BEFORE its final norm, ``stream`` [B, T, D] (under
    hyper-connections the SUM of its streams), and the embedding of
    token ``t+1`` (``targets[t]``), each under a norm of its own, side by
    side through ``eh_proj``; the module's layers
    (``layer_plan(mtp=True)``: the same layer programs, its own
    weights)."""
    mp, dt = params["mtp"], c.compute_dtype
    nxt = _embed(params, targets, c)
    with scope("hvd.mtp"):
        m = jnp.concatenate(
            [_rms(nxt, mp["token_norm"].astype(dt), c.norm_eps),
             _rms(stream, mp["hidden_norm"].astype(dt), c.norm_eps)], -1) \
            @ mp["eh_proj"].astype(dt)
    m, _ = _run_layers(mp, _constrain(m, mesh), c, mesh, seq_axis,
                       mtp=True)
    return _final_norm(mp, m, c)


def _mtp_loss(params, stream, batch, c, mesh, seq_axis):
    """The multi-token-prediction term (DeepSeek-V3, section 2.2, one
    module): :func:`_mtp_hidden` through the main model's head; the
    cross-entropy against token ``t+2`` (``targets[t+1]``) over the
    positions that have one (a sequence's last has none). The expert
    layers' balance statistics of the module join no auxiliary term."""
    targets = batch["targets"]
    nll = _head_nll(params, _mtp_hidden(params, stream, targets, c, mesh,
                                        seq_axis),
                    jnp.roll(targets, -1, axis=1), c)
    with scope("hvd.mtp"):
        has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
        mask = has_target.astype(jnp.float32) * (
            1.0 if batch.get("mask") is None
            else batch["mask"].astype(jnp.float32))
        mask = jnp.broadcast_to(mask, nll.shape)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@scope("hvd.loss")
def _token_nll(logits, targets):
    """Per-token negative log-likelihood in logsumexp form: no second
    [B,T,vocab] f32 array for log_softmax — at bench shapes that array
    alone is GBs of HBM. The ONE cross-entropy used by llama_loss and
    the 1F1B last-stage loss head."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0]
    return lse - picked


def _token_nll_in_blocks(params, x, targets, c):
    """:func:`_token_nll` of the head's logits without the logits: the
    tokens of ``x`` [B, T, D] in blocks of ``c.loss_chunk`` (or the
    largest divisor of B*T under it), each block's float32 logits,
    logsumexp and picked logit under a checkpoint inside a ``lax.map``:
    forward and backward hold one block's logits ([block, vocab]
    float32) and their cotangent, and the head's matrix gradient is the
    sum of the blocks'."""
    from horovod_tpu.ops.flash_attention import _pick_block

    n = targets.size
    rows = _pick_block(n, c.loss_chunk)
    # What the blocks read of the parameters, cast once: a block's
    # cotangent is added to ONE accumulator in the compute dtype.
    name = "embed" if c.tie_embeddings else "lm_head"
    head = {name: params[name].astype(c.compute_dtype)}

    @jax.checkpoint
    def block(xt):
        xb, tb = xt
        return _token_nll(_head(head, xb, c), tb)

    nll = lax.map(block, (x.reshape(n // rows, rows, x.shape[-1]),
                          targets.reshape(n // rows, rows)))
    return nll.reshape(targets.shape)


def llama_pipeline_programs(config, mesh=None, seq_axis="seq", *,
                            microbatches=1, denom=1.0):
    """Build ``(stage_fn, loss_fn, aux_cotangent)`` — the exact per-
    stage program and last-stage loss head the 1F1B pipeline engines
    run (also the gpipe stage body via the same ``_stage_scan``).

    This is the program-builder hook hvdlint traces: combined with
    ``parallel.pipeline.build_pipeline_inner`` it reconstructs the real
    per-device pipeline program for static analysis (C5 schedule
    conformance — see ``horovod_tpu/analysis/``) without needing a
    mesh, devices, or shard_map. ``denom`` is the global mask-token
    denominator folded into each microbatch's loss numerator (a traced
    value inside the real step; any static float for lint purposes).
    Used by :func:`_llama_loss_1f1b` itself so the two can never drift.
    """
    c = config
    dt = c.compute_dtype
    stage_fn = _stage_scan(
        _build_layer_body(c, mesh, seq_axis, constrain_acts=False))

    def loss_fn(hp, y_mb, la):
        final_norm, lm_head = hp
        tgt, m = la
        h = _rmsnorm(y_mb, final_norm.astype(dt), c.norm_eps)
        logits = jnp.matmul(h, lm_head.astype(dt),
                            preferred_element_type=jnp.float32)
        return jnp.sum(_token_nll(logits, tgt) * m) / denom

    aux_ct = (c.moe_aux_weight / (c.n_layers * microbatches)
              if c.n_experts > 0 else 0.0)
    return stage_fn, loss_fn, aux_ct


def _llama_loss_1f1b(params, batch, c, mesh, seq_axis, n_stages):
    """Training loss through a fused-backward pipeline schedule —
    lockstep "1f1b" or the virtual-stage "interleaved_1f1b".

    The schedule computes loss AND gradients in one combined scan
    (parallel.pipeline.one_f_one_b / interleaved_one_f_one_b); a
    ``custom_vjp`` hands those gradients to the outer
    ``jax.value_and_grad`` so callers keep the ordinary llama_loss
    contract. The MoE aux objective is folded into the schedule's
    backward via its constant per-contribution cotangent
    (moe_aux_weight / (n_layers * M)) — identical math to the gpipe
    path's ``loss + w * mean(aux)``. For the interleaved schedule the
    stacked layer axis is split into ``n_stages * V`` chunks and
    device ``s`` holds the non-contiguous chunks ``v*S + s`` (the
    engine permutes/unpermutes internally, so params and grads stay in
    canonical layer order here).
    """
    from horovod_tpu.parallel.pipeline import (
        interleaved_one_f_one_b,
        one_f_one_b,
    )

    dt = c.compute_dtype
    b, t = batch["tokens"].shape
    M = _validate_pipeline(c, b, mesh, seq_axis, n_stages)

    tokens = batch["tokens"]
    if mesh is not None:
        tokens = lax.with_sharding_constraint(
            tokens, jax.sharding.NamedSharding(
                mesh, P(("data", "fsdp"), "seq")))

    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones((b, t), jnp.float32)
    mask = mask.astype(jnp.float32)
    # The mask denominator is global across microbatches, so it is
    # computed OUTSIDE the schedule and folded into each microbatch's
    # loss numerator (mask is data, not a differentiated value).
    denom = jnp.maximum(jnp.sum(mask), 1.0)

    stage_fn, loss_fn, aux_ct = llama_pipeline_programs(
        c, mesh, seq_axis, microbatches=M, denom=denom)

    def schedule_fwd(sp, hp, xs, largs):
        if c.pipeline_schedule == "interleaved_1f1b":
            loss, aux, d_sp, d_hp, d_xs = interleaved_one_f_one_b(
                stage_fn, loss_fn, sp, hp, xs, largs, mesh,
                num_virtual=c.pipeline_virtual_stages,
                aux_cotangent=aux_ct)
        else:
            loss, aux, d_sp, d_hp, d_xs = one_f_one_b(
                stage_fn, loss_fn, sp, hp, xs, largs, mesh,
                aux_cotangent=aux_ct)
        return loss + aux_ct * aux, (d_sp, d_hp, d_xs, largs)

    def schedule_primal(sp, hp, xs, largs):
        # VALUE-ONLY path (eval loops, loss logging): the gpipe forward
        # plus the shared loss head. one_f_one_b computes every
        # gradient to produce its value, so routing no-grad calls
        # through it costs ~3x the needed work (ADVICE r5); under
        # differentiation custom_vjp uses schedule_fwd instead. Same
        # stage_fn, same loss_fn, same aux folding — equality of the
        # two values is the gpipe-vs-1f1b loss identity
        # tests/single/test_pipeline_1f1b.py pins.
        from horovod_tpu.parallel.pipeline import gpipe

        ys, aux_total = gpipe(stage_fn, sp, xs, mesh)
        losses = jax.vmap(loss_fn, in_axes=(None, 0, 0))(hp, ys, largs)
        return jnp.sum(losses) + aux_ct * aux_total

    schedule = jax.custom_vjp(schedule_primal)

    def schedule_bwd(res, dl):
        import numpy as _np

        d_sp, d_hp, d_xs, largs = res
        scale = lambda g: jax.tree.map(  # noqa: E731
            lambda x: (x * dl).astype(x.dtype), g)
        d_largs = jax.tree.map(
            lambda x: (jnp.zeros_like(x)
                       if jnp.issubdtype(x.dtype, jnp.inexact)
                       else _np.zeros(x.shape, jax.dtypes.float0)),
            largs)
        return scale(d_sp), scale(d_hp), scale(d_xs), d_largs

    schedule.defvjp(schedule_fwd, schedule_bwd)

    x = _constrain(_embed(params, tokens, c), mesh)
    xs = x.reshape(M, b // M, t, x.shape[-1])
    largs = (batch["targets"].reshape(M, b // M, t),
             mask.reshape(M, b // M, t))
    return schedule(params["layers"],
                    (params["final_norm"], params["lm_head"]), xs,
                    largs)
