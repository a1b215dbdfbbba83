"""Plain float32 references of the architectures the llama path runs
beyond the dense decoder. What the program's kernels, sorts, scans and
remat modes are compared against (tests/single/test_olmoe_reference.py,
tests/single/test_afmoe_reference.py, tests/single/test_lfm2_reference.py,
tests/single/test_qwen3next_reference.py,
tests/single/test_jamba_reference.py, test_nemotronh_reference.py,
test_sala_reference.py, test_xing4_reference.py; the chip benchmark
keeps copies of its own, chipbench/models/olmoe.py, afmoe.py,
lfm2moe.py, qwen3next.py, jamba.py, nemotronh.py, minicpmsala.py and
xing4.py). OLMoE first; Trinity-Mini (afmoe), LFM2-8B-A1B (lfm2_moe),
Qwen3-Next-80B-A3B (qwen3_next), AI21-Jamba2-3B (jamba),
Nemotron-3-Super (nemotron_h), MiniCPM-SALA (minicpm_sala) and
Xing4.0-29B-A4B (xing4_0) below it, each with its own description.

OLMoE (arXiv:2409.02060; Hugging Face ``modeling_olmoe.py``), as
published:

- pre-norm residual block, ``x += Attn(RMSNorm(x))``,
  ``x += MoE(RMSNorm(x))``;
- attention: ``q = RMSNorm_q(W_q h)``, ``k = RMSNorm_k(W_k h)``, each
  norm over the WHOLE projected width, before the split into heads;
  half-split RoPE; causal ``softmax(q k / sqrt(head_dim)) v``; ``W_o``;
- experts: router logits ``W_r h``, softmax in float32 over all
  experts, the K largest probabilities and their experts, renormalised
  only where ``norm_topk_prob`` (OLMoE: not),
  ``y = sum_k p_k W_down,k(silu(W_gate,k h) * W_up,k h)``;
- loss: token cross-entropy + ``moe_aux_weight`` x the load-balancing
  term over ALL layers' tokens pooled,
  ``E * sum_{slot,e} f_{slot,e} P_e`` (``f`` the share of tokens whose
  ``slot``-th choice is ``e``, ``P`` the mean probability of ``e``).

Written to share nothing with ``models/llama.py`` or
``ops/grouped_moe.py``: an explicit mask, a Python loop over layers,
every expert computed for every token and weighted by its routing
probability (zero for the experts not chosen), the K choices found by
K arg-maxes; no kernel, no sort, no scan, no remat. It reads the
program's parameter tree and the ``LlamaConfig`` fields as data.

Departures from the published description:

- the paper's router z-loss (coefficient 0.001) is not in the public
  ``config.json`` and not in Hugging Face's loss: left out;
- parameters stored in bf16 are read as float32 (exact); everything
  after that is float32 at ``"highest"`` matmul precision;
- Hugging Face weights the balance statistics by the attention mask
  where one is given; here, as in the program, a loss mask leaves the
  aux term alone: every position counts in it.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def _top_k_weights(probs, k, renormalise):
    """probs [..., E] -> (weights [..., E]: the probability of each of
    the k largest, 0 elsewhere; choices [..., k, E] one-hot)."""
    n = probs.shape[-1]
    left, weights, choices = probs, jnp.zeros_like(probs), []
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        choices.append(pick)
        weights = weights + pick * probs
        left = jnp.where(pick > 0, -1.0, left)
    if renormalise:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights, jnp.stack(choices, -2)


def olmoe_forward(params, tokens, cfg):
    """tokens [B, T] -> (logits [B, T, vocab] f32, aux): the published
    forward pass and load-balancing term (see the module docstring)."""
    hd = cfg.d_model // cfg.n_heads
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    inv = cfg.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32)
                             / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv           # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    mask = jnp.tril(jnp.ones((t, t), bool))
    all_probs, all_choices = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda w: w[i].astype(F32),
                              params["layers"])
            h = _rms(x, lp["attn_norm"], cfg.norm_eps)
            q = _rms(h @ lp["wq"], lp["q_norm"], cfg.norm_eps)
            k = _rms(h @ lp["wk"], lp["k_norm"], cfg.norm_eps)
            q = rope(q.reshape(b, t, cfg.n_heads, hd))
            k = rope(k.reshape(b, t, cfg.n_kv_heads, hd))
            v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
            x = x + a @ lp["wo"]

            h = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            probs = jax.nn.softmax(h @ lp["router"], -1)     # [B,T,E]
            weights, choices = _top_k_weights(
                probs, cfg.n_experts_per_token, cfg.norm_topk_prob)
            act = jax.nn.silu(jnp.einsum("btd,edf->btef", h,
                                         lp["moe_gate"])) \
                * jnp.einsum("btd,edf->btef", h, lp["moe_up"])
            y = jnp.einsum("btef,efd->bted", act, lp["moe_down"])
            x = x + jnp.einsum("bte,bted->btd", weights, y)
            all_probs.append(probs)
            all_choices.append(choices)
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        logits = x @ params["lm_head"].astype(F32)
    # All layers' tokens pooled, as load_balancing_loss_func does.
    n = cfg.n_experts
    f = jnp.mean(jnp.concatenate(all_choices, 0).reshape(
        -1, cfg.n_experts_per_token, n), 0)                 # [K, E]
    prob = jnp.mean(jnp.concatenate(all_probs, 0).reshape(-1, n), 0)
    return logits, n * jnp.sum(f * prob[None, :])


def olmoe_loss(params, batch, cfg):
    """Token cross-entropy, the mean over the positions ``batch["mask"]``
    keeps (all without one), + ``cfg.moe_aux_weight`` x the aux term;
    ``jax.grad`` of this is the reference gradient."""
    logits, aux = olmoe_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask) + cfg.moe_aux_weight * aux


# ---------------------------------------------------------------------
# Trinity-Mini (arcee-ai, ``model_type`` ``afmoe``; Hugging Face
# ``modeling_afmoe.py``), as published. ``RMS`` is an RMSNorm with its
# own gain, eps ``norm_eps``:
#
# - embedding: ``x = E[tokens] * sqrt(d_model)`` (``mup_enabled``);
# - attention of layer l: ``h = RMS_in(x)``; ``q = RMS_q(W_q h)``,
#   ``k = RMS_k(W_k h)`` with the norm over EACH head's ``head_dim`` (one
#   gain of ``head_dim`` a projection and layer, shared by the heads),
#   ``v = W_v h``, ``g = W_g h``; half-split RoPE on q and k where
#   ``layer_types[l]`` is ``sliding_attention``, NONE where it is
#   ``full_attention``; key j is visible to query i where ``j <= i``, and
#   on a sliding layer also ``j > i - sliding_window``;
#   ``a = softmax(q k / sqrt(head_dim)) v``;
#   ``x = x + RMS_post_attn(W_o (a * sigmoid(g)))``;
# - FFN of layer l: ``h = RMS_pre_mlp(x)``; a dense layer
#   (``l < n_dense_layers``) ``y = W_down(silu(W_gate h) * W_up h)``; an
#   expert layer ``s = sigmoid(W_r h)`` over all experts, the K experts
#   with the largest ``s + expert_bias``, ``w_k = route_scale * s_k /
#   (sum of the chosen s + 1e-20)``, ``y = Shared(h) + sum_k w_k
#   Expert_k(h)``, each a SwiGLU; ``x = x + RMS_post_mlp(y)``;
# - ``logits = W_head RMS_final(x)``; loss = mean token cross-entropy.
#
# The share. Where ``cfg.n_experts_held`` is set the parameter tree holds
# the matrices of experts ``first_expert .. first_expert + held - 1``
# only: the router still scores and chooses over all ``n_experts``, and
# the sum runs over the chosen experts that are held. What the absent
# ones would add is left out and that partial result goes on to the next
# layer, as in the program. A sliced vocabulary is a smaller vocabulary
# (``vocab_rows`` of :func:`afmoe_loss` cuts an uncut model's logits the
# same way).
#
# Departures: Hugging Face's forward returns no router loss and
# ``config.json`` gives ``load_balance_coeff`` without a formula: no aux
# term; ``expert_bias``'s update rule is the trainer's, not the model's:
# it is read as data. Parameters stored in bf16 are read as float32.
# Written like ``olmoe_forward``: explicit masks, a Python loop over
# layers, every held expert computed for every token and weighted (zero
# where not chosen), nothing shared with models/llama.py or ops/.
# ---------------------------------------------------------------------

def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def afmoe_route(h, lp, cfg):
    """``h`` [..., D] -> weights [..., E] over ALL experts: ``route_scale
    * s / (sum of the chosen s + 1e-20)`` at the K experts with the
    largest ``s + expert_bias``, 0 elsewhere."""
    n = cfg.n_experts
    s = jax.nn.sigmoid(h @ lp["router"])
    left, chosen = s + lp["expert_bias"], jnp.zeros_like(s)
    for _ in range(cfg.n_experts_per_token):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = chosen * s
    return cfg.route_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def afmoe_expert_layer(h, lp, cfg):
    """The FFN of one expert layer on normalized ``h`` [B, T, D], in two
    parts: ``(Shared(h), the routed sum over the experts ``lp`` holds)``.
    ``lp`` is one layer's float32 parameters; its expert matrices hold
    experts ``first_expert .. + n_experts_held - 1`` (all, where no
    share is set)."""
    first = cfg.first_expert
    held = cfg.n_experts_held or cfg.n_experts
    w = afmoe_route(h, lp, cfg)[..., first:first + held]
    act = jax.nn.silu(jnp.einsum("btd,edf->btef", h, lp["moe_gate"])) \
        * jnp.einsum("btd,edf->btef", h, lp["moe_up"])
    y = jnp.einsum("btef,efd->bted", act, lp["moe_down"])
    return (_swiglu(h, lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"]),
            jnp.einsum("bte,bted->btd", w, y))


def afmoe_forward(params, tokens, cfg):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above). ``params`` is the program's tree: ``dense_layers`` and
    ``layers`` stacked, any storage dtype."""
    hd = cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    inv = cfg.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32)
                             / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv           # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens] * (cfg.d_model ** 0.5)
        for l in range(cfg.n_layers):
            dense = l < cfg.n_dense_layers
            stack, at = (params["dense_layers"], l) if dense \
                else (params["layers"], l - cfg.n_dense_layers)
            lp = jax.tree.map(lambda w: w[at].astype(F32), stack)
            sliding = cfg.layer_types[l] == "sliding_attention"
            h = _rms(x, lp["attn_norm"], cfg.norm_eps)
            q = _rms((h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd),
                     lp["q_norm"], cfg.norm_eps)
            k = _rms((h @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, hd),
                     lp["k_norm"], cfg.norm_eps)
            v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
            mask = j <= i
            if sliding:
                q, k = rope(q), rope(k)
                mask = mask & (j > i - cfg.sliding_window)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
            a = (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]
            x = x + _rms(a, lp["post_attn_norm"], cfg.norm_eps)

            h = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            if dense:
                y = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                y = sum(afmoe_expert_layer(h, lp, cfg))
            x = x + _rms(y, lp["post_mlp_norm"], cfg.norm_eps)
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return x @ params["lm_head"].astype(F32)


def afmoe_loss(params, batch, cfg, vocab_rows=None):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one); no aux term. ``vocab_rows``: the loss over
    the first that many rows of the vocabulary, the other logits
    removed (what a chip that holds that slice of the head computes).
    ``jax.grad`` of this is the reference gradient."""
    logits = afmoe_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``; Hugging Face
# ``modeling_lfm2_moe.py``), as published. Pre-norm, two RMSNorms a
# layer (``operator_norm`` and ``ffn_norm`` there; ``conv_norm`` or
# ``attn_norm``, and ``mlp_norm`` in the program's tree), eps
# ``norm_eps``, no bias anywhere: ``x = x + Mixer(RMS(x))``, then
# ``x = x + FFN(RMS(x))``.
#
# - a ``conv`` layer's mixer, the gated short convolution:
#   ``[B, C, z] = split3(W_in h)`` (``W_in`` d -> 3d); ``u = B * z``;
#   ``c_t = sum_{j=0..L-1} w_j * u_{t-(L-1)+j}`` with ``u`` zero before
#   position 0 (depthwise: one weight a channel and tap, ``conv_w``
#   [L, d], ``L = conv_L_cache`` = 3; causal: ``nn.Conv1d`` with
#   ``groups = d``, padding ``L - 1``, the tail cut); ``y = W_out (C *
#   c)``. No position encoding, no softmax, no state beyond L-1 tokens;
# - a ``full_attention`` layer's: ``q = RMS_q(W_q h)``, ``k = RMS_k(W_k
#   h)`` over EACH head's ``head_dim`` (one gain a projection and layer,
#   shared by the heads), ``v = W_v h``; half-split RoPE on q and k;
#   causal ``softmax(q k / sqrt(head_dim)) v`` with grouped key/value
#   heads; ``W_o``. No gate, no post-norm;
# - FFN: the first ``n_dense_layers`` layers (all, in the family's dense
#   models, which have no experts) a SwiGLU of width ``d_ff``; the
#   others ``s = sigmoid(W_r h)`` over all experts, the K experts
#   with the largest ``s + expert_bias``, ``w_k = route_scale * s_k /
#   (sum of the chosen s + 1e-6)``, ``y = sum_k w_k Expert_k(h)``, each
#   a SwiGLU of width ``moe_d_ff``. No shared expert;
# - ``logits = E RMS_final(x)``: the head is the embedding matrix ``E``
#   (``tie_embedding``); loss = mean token cross-entropy.
#
# The share: as for afmoe above (``n_experts_held``, ``first_expert``;
# the vocabulary rows held are a smaller vocabulary).
#
# Departures: no router aux loss (the config has no coefficient);
# ``expert_bias`` is read as data (its update is the trainer's); the
# program's router guards the sum of the chosen scores with ``max(.,
# 1e-9)`` where the published form adds 1e-6: this reference follows the
# published form, and the two differ by under 1e-6 of a weight (four
# sigmoids sum to about 2). Parameters stored in bf16 are read as
# float32. Written like the two above: explicit mask, explicit shifts, a
# Python loop over layers, every held expert computed for every token,
# nothing shared with models/llama.py or ops/. It finds a layer's
# parameters in the program's tree by its own count (``_lfm2_layer``),
# not by ``LlamaConfig.layer_plan``.
# ---------------------------------------------------------------------

def lfm2_short_conv(h, lp):
    """The conv mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters: three explicit shifted products."""
    b, t, d = h.shape
    bcz = h @ lp["conv_in"]
    gate_in, gate_out, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    u = gate_in * z
    taps = lp["conv_w"].shape[0]
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                  # u as it was ``back`` ago
        past = jnp.concatenate(
            [jnp.zeros((b, back, d), F32), u[:, :t - back]], 1)
        conv = conv + lp["conv_w"][j] * past
    return (gate_out * conv) @ lp["conv_out"]


def lfm2_route(h, lp, cfg):
    """``h`` [..., D] -> weights [..., E] over ALL experts: ``route_scale
    * s / (sum of the chosen s + 1e-6)`` at the K experts with the
    largest ``s + expert_bias``, 0 elsewhere."""
    n = cfg.n_experts
    s = jax.nn.sigmoid(h @ lp["router"])
    left, chosen = s + lp["expert_bias"], jnp.zeros_like(s)
    for _ in range(cfg.n_experts_per_token):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = chosen * s
    return cfg.route_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-6)


def lfm2_expert_layer(h, lp, cfg):
    """The FFN of one expert layer on normalized ``h`` [B, T, D]: the
    routed sum over the experts ``lp`` holds (``first_expert .. +
    n_experts_held - 1``; all, where no share is set)."""
    first = cfg.first_expert
    held = cfg.n_experts_held or cfg.n_experts
    w = lfm2_route(h, lp, cfg)[..., first:first + held]
    act = jax.nn.silu(jnp.einsum("btd,edf->btef", h, lp["moe_gate"])) \
        * jnp.einsum("btd,edf->btef", h, lp["moe_up"])
    y = jnp.einsum("btef,efd->bted", act, lp["moe_down"])
    return jnp.einsum("bte,bted->btd", w, y)


def _lfm2_layer(params, cfg, l):
    """Layer ``l``'s parameters out of the program's tree, float32: the
    conv layers and the attention layers are stacked apart, and the
    leading dense ones apart from both."""
    def stack(i):
        return ("dense_" if i < cfg.n_dense_layers else "") \
            + ("conv_" if cfg.layer_types[i] == "conv" else "") + "layers"

    at = sum(stack(i) == stack(l) for i in range(l))
    return jax.tree.map(lambda w: w[at].astype(F32), params[stack(l)])


def lfm2_forward(params, tokens, cfg):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above). ``params`` is the program's tree, any storage dtype."""
    hd = cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    inv = cfg.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32)
                             / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv           # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    embed = params["embed"].astype(F32)
    with jax.default_matmul_precision("highest"):
        x = embed[tokens]
        for l in range(cfg.n_layers):
            lp = _lfm2_layer(params, cfg, l)
            if cfg.layer_types[l] == "conv":
                x = x + lfm2_short_conv(
                    _rms(x, lp["conv_norm"], cfg.norm_eps), lp)
            else:
                h = _rms(x, lp["attn_norm"], cfg.norm_eps)
                q = rope(_rms((h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd),
                              lp["q_norm"], cfg.norm_eps))
                k = rope(_rms((h @ lp["wk"]).reshape(
                    b, t, cfg.n_kv_heads, hd), lp["k_norm"], cfg.norm_eps))
                v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
                k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
                x = x + a @ lp["wo"]
            h = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            if l < cfg.n_dense_layers or not cfg.n_experts:
                x = x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                x = x + lfm2_expert_layer(h, lp, cfg)
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return x @ embed.T


def lfm2_loss(params, batch, cfg, vocab_rows=None):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one); no aux term. ``vocab_rows``: the loss over
    the first that many rows of the vocabulary, the other logits
    removed. ``jax.grad`` of this is the reference gradient; the tied
    matrix's is the sum of its two uses."""
    logits = lfm2_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# Qwen3-Next-80B-A3B (Qwen, ``model_type`` ``qwen3_next``; Hugging Face
# ``modeling_qwen3_next.py``), as published. Pre-norm, two RMSNorms a
# layer and one after the last, eps ``rms_norm_eps``, no bias anywhere:
# ``x = x + Mixer(RMS(x))``, then ``x = x + MoE(RMS(x))``; every layer
# is an expert layer. The norms of the residual stream and the q/k norms
# are the zero-centred form ``x / rms(x) * (1 + w)``; the program's tree
# stores ``g = 1 + w`` (the same function and gradient), which is what
# this reference reads.
#
# - a ``linear_attention`` layer's mixer, Gated DeltaNet (``Hk`` key
#   heads and ``Hv`` value heads of ``dk`` and ``dv``):
#   1. ``[q, k, v, z] = h W_in`` (``gdn_in``, columns ``[q | k | v |
#      z]``, each head by head), ``[b, a] = h W_ba`` (``gdn_ba``, ``[b |
#      a]``). The published checkpoint groups the same columns by key
#      head: a permutation of columns;
#   2. ``u = [q, k, v]``; ``u' = SiLU(conv(u))``, depthwise, causal,
#      ``conv_taps`` taps (``gdn_conv`` [taps, channels]), zero before
#      position 0, no bias;
#   3. ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a +
#      dt_bias)``, one scalar a value head and token;
#   4. ``q``, ``k`` L2-normalised over ``dk`` (``x * rsqrt(sum x^2 +
#      1e-6)``), key head ``i`` serving value heads ``i * Hv/Hk .. +
#      Hv/Hk - 1``; ``q`` scaled by ``dk^-1/2``;
#   5. a value head's state ``S`` [dk, dv], ``S_0 = 0``; for each token
#      ``t``: ``S <- exp(g_t) S``; ``r_t = v_t - S^T k_t``; ``S <- S +
#      k_t (beta_t r_t)^T``; ``o_t = S^T q_t``;
#   6. ``y = RMS_dv(o; w) * SiLU(z)`` a head (this norm's gain is plain
#      ``w``), then ``y W_o`` (``gdn_out``);
# - a ``full_attention`` layer's: ``[q, gate] = h W_q`` (the program's
#   tree: ``wq`` and ``wg``, the same linear map under another layout),
#   ``k = h W_k``, ``v = h W_v``; RMSNorm of ``q`` and ``k`` over each
#   head's ``head_dim``, one gain shared by the heads; half-split RoPE
#   on the FIRST ``partial_rotary`` dimensions of a head, the rest pass;
#   causal ``softmax(q k / sqrt(head_dim)) v`` with grouped key/value
#   heads; ``(attn * sigmoid(gate)) W_o``;
# - the expert layer: ``p = softmax(h W_r)`` over all experts, the K
#   largest renormalised to sum 1 (``norm_topk_prob``), ``y = sum_k p_k
#   SwiGLU_k(h) + sigmoid(h w_sg) * SwiGLU_shared(h)`` (``shared_score``
#   [D, 1]);
# - ``logits = RMS_final(x) W_head``, untied; loss = mean token
#   cross-entropy.
#
# The share: as for afmoe above. Departures: no router aux loss (the
# catalog's row gives no coefficient); the multi-token-prediction head
# is left out; parameters stored in bf16 are read as float32. Step 5 is
# a ``lax.scan`` over TOKENS exactly as written: no chunks, no WY form,
# nothing of ops/gated_delta_rule.py.
# ---------------------------------------------------------------------

def qwen3next_delta_rule(q, k, v, g, beta):
    """Step 5 for ``q``, ``k`` [B, T, H, dk], ``v`` [B, T, H, dv], ``g``,
    ``beta`` [B, T, H], float32, one state a value head -> ``o`` [B, T,
    H, dv]. Products as multiply-and-sum: float32 on any device."""
    def token(S, x):
        q, k, v, g, beta = x                         # [B, H, ...]
        S = jnp.exp(g)[..., None, None] * S
        r = v - jnp.sum(S * k[..., None], -2)
        S = S + k[..., None] * (beta[..., None] * r)[..., None, :]
        return S, jnp.sum(S * q[..., None], -2)

    b, _, h, dk = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def qwen3next_gated_delta_net(h, lp, cfg, beta_max=1.0):
    """The ``linear_attention`` mixer on normalized ``h`` [B, T, D] with
    one layer's float32 parameters: steps 1-6 above. ``beta_max``: step
    3's write strength is ``beta_max * sigmoid(b)`` (Olmo-Hybrid's 2;
    1 is the published Qwen3-Next)."""
    b, t, _ = h.shape
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    kw, vw = hk * dk, hv * dv
    qkvz, ba = h @ lp["gdn_in"], h @ lp["gdn_ba"]
    u, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
    taps, conv = lp["gdn_conv"].shape[0], jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                  # u as it was ``back`` ago
        conv = conv + lp["gdn_conv"][j] * jnp.concatenate(
            [jnp.zeros((b, back, u.shape[-1]), F32), u[:, :t - back]], 1)
    u = jax.nn.silu(conv)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(u[..., :kw].reshape(b, t, hk, dk)) * dk ** -0.5
    k = unit(u[..., kw:2 * kw].reshape(b, t, hk, dk))
    v = u[..., 2 * kw:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    if beta_max != 1.0:
        beta = beta_max * beta
    g = -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(
        ba[..., hv:] + lp["gdn_dt_bias"])
    o = qwen3next_delta_rule(jnp.repeat(q, hv // hk, 2),
                             jnp.repeat(k, hv // hk, 2), v, g, beta)
    y = _rms(o, lp["gdn_out_norm"], cfg.norm_eps) \
        * jax.nn.silu(z.reshape(b, t, hv, dv))
    return y.reshape(b, t, vw) @ lp["gdn_out"]


def qwen3next_expert_layer(h, lp, cfg):
    """The FFN of one layer on normalized ``h`` [B, T, D]: the routed
    sum over the experts ``lp`` holds (``first_expert .. +
    n_experts_held - 1``; all, where no share is set) plus the gated
    shared expert."""
    first = cfg.first_expert
    held = cfg.n_experts_held or cfg.n_experts
    w, _ = _top_k_weights(jax.nn.softmax(h @ lp["router"], -1),
                          cfg.n_experts_per_token, cfg.norm_topk_prob)
    act = jax.nn.silu(jnp.einsum("btd,edf->btef", h, lp["moe_gate"])) \
        * jnp.einsum("btd,edf->btef", h, lp["moe_up"])
    y = jnp.einsum("btef,efd->bted", act, lp["moe_down"])
    return jnp.einsum("bte,bted->btd", w[..., first:first + held], y) \
        + jax.nn.sigmoid(h @ lp["shared_score"]) * _swiglu(
            h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def _qwen3next_layer(params, cfg, l):
    """Layer ``l``'s parameters out of the program's tree, float32: the
    linear_attention layers and the full_attention layers are stacked
    apart (by this file's own count, not ``LlamaConfig.layer_plan``)."""
    def stack(i):
        return ("linear_" if cfg.layer_types[i] == "linear_attention"
                else "") + "layers"

    at = sum(stack(i) == stack(l) for i in range(l))
    return jax.tree.map(lambda w: w[at].astype(F32), params[stack(l)])


def qwen3next_forward(params, tokens, cfg):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above). ``params`` is the program's tree, any storage dtype."""
    hd, rot = cfg.head_dim, cfg.partial_rotary or cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    inv = cfg.rope_theta ** (-jnp.arange(0, rot // 2, dtype=F32)
                             / (rot // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv           # [T, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos, rest], -1)

    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        for l in range(cfg.n_layers):
            lp = _qwen3next_layer(params, cfg, l)
            if cfg.layer_types[l] == "linear_attention":
                x = x + qwen3next_gated_delta_net(
                    _rms(x, lp["gdn_norm"], cfg.norm_eps), lp, cfg)
            else:
                h = _rms(x, lp["attn_norm"], cfg.norm_eps)
                q = rope(_rms((h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd),
                              lp["q_norm"], cfg.norm_eps))
                k = rope(_rms((h @ lp["wk"]).reshape(
                    b, t, cfg.n_kv_heads, hd), lp["k_norm"], cfg.norm_eps))
                v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
                k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
                x = x + (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]
            x = x + qwen3next_expert_layer(
                _rms(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg)
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return x @ params["lm_head"].astype(F32)


def qwen3next_loss(params, batch, cfg, vocab_rows=None):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one); no aux term. ``vocab_rows``: the loss over
    the first that many rows of the vocabulary, the other logits
    removed. ``jax.grad`` of this is the reference gradient."""
    logits = qwen3next_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# AI21-Jamba2-3B (AI21 Labs, ``model_type`` ``jamba``; Hugging Face
# ``modeling_jamba.py``), as published. Pre-norm, two RMSNorms a layer
# and one after the last, eps ``rms_norm_eps``, NO position encoding
# anywhere: ``x = x + Mixer(RMS(x))``, then ``x = x + FFN(RMS(x))``.
# Layer ``i`` is an attention layer where ``i % attn_layer_period ==
# attn_layer_offset`` (14 and 7), else a Mamba layer (``layer_types``
# ``full_attention`` and ``mamba`` in the program's configuration).
#
# - a ``mamba`` layer's mixer (``d_inner = mamba_expand * hidden``
#   channels, ``N = mamba_d_state`` states a channel, ``R =
#   mamba_dt_rank``), a sequence at a time:
#   1. ``[u, z] = h W_in`` (``ssm_in``, columns ``[u | z]``), no bias;
#   2. ``u = SiLU(conv(u) + b_conv)``: depthwise, causal,
#      ``mamba_d_conv`` taps (``ssm_conv`` [taps, channels], tap ``j``
#      meeting ``u_{t - (taps-1) + j}``, zero before position 0;
#      ``nn.Conv1d`` with ``groups = d_inner``, padding ``taps - 1``, the
#      tail cut), bias ``ssm_conv_bias`` (``mamba_conv_bias``);
#   3. ``[r, B, C] = u W_x`` (``ssm_x``, columns ``[r | B | C]``, R, N
#      and N wide), no bias; each under an RMSNorm of its own
#      (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``: gains
#      ``ssm_dt_norm``, ``ssm_b_norm``, ``ssm_c_norm``);
#   4. ``dt = softplus(r W_dt + b_dt)`` (``ssm_dt``, ``ssm_dt_bias``);
#      ``A = -exp(A_log)`` (``ssm_a_log`` [channels, N]);
#   5. a channel's state ``s`` [N], ``s_0 = 0``; for each token ``t``:
#      ``s_t = exp(dt_t A) * s_{t-1} + dt_t u_t B_t``;
#      ``y_t = s_t . C_t + D u_t`` (``ssm_d``);
#   6. ``(y * SiLU(z)) W_out`` (``ssm_out``), no bias;
# - an attention layer's: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``,
#   ``n_heads`` query heads on ``n_kv_heads`` key/value heads (20 on 1),
#   causal ``softmax(q k / sqrt(head_dim)) v``, ``W_o``. No RoPE, no
#   bias, no gate, no q/k norm;
# - FFN: a SwiGLU of width ``d_ff`` in EVERY layer (``num_experts`` 1:
#   ``expert_layer_offset`` / ``_period`` then select layers whose one
#   "expert" is the same SwiGLU; no router, no auxiliary loss);
# - ``logits = E RMS_final(x)``: the head is the embedding matrix ``E``
#   (``tie_word_embeddings``); loss = mean token cross-entropy.
#
# Departures: parameters stored in bf16 are read as float32;
# ``num_logits_to_keep`` and ``use_mamba_kernels`` are runtime keys and
# change no result. Step 5 is a ``lax.scan`` over TOKENS exactly as
# written: no chunk, no kernel, nothing of ops/selective_scan.py. It
# finds a layer's parameters in the program's tree by its own count.
# ---------------------------------------------------------------------

def jamba_selective_scan(u, dt, A, Bm, Cm, D):
    """Step 5 for ``u``, ``dt`` [B, T, C], ``A`` [C, N], ``Bm``, ``Cm``
    [B, T, N], ``D`` [C], float32 -> ``y`` [B, T, C]."""
    def token(s, x):
        u, dt, Bt, Ct = x                       # [B, C], [B, C], [B, N]
        s = jnp.exp(dt[..., None] * A) * s \
            + (dt * u)[..., None] * Bt[:, None, :]
        return s, jnp.sum(s * Ct[:, None, :], -1) + D * u

    b, _, c = u.shape
    _, y = jax.lax.scan(
        token, jnp.zeros((b, c, A.shape[1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def jamba_mamba_mixer(h, lp, cfg):
    """The mamba mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters (steps 1-6 above)."""
    b, t, _ = h.shape
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    uz = h @ lp["ssm_in"]
    di = uz.shape[-1] // 2
    u, z = uz[..., :di], uz[..., di:]
    taps, conv = lp["ssm_conv"].shape[0], jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                  # u as it was ``back`` ago
        conv = conv + lp["ssm_conv"][j] * jnp.concatenate(
            [jnp.zeros((b, back, di), F32), u[:, :t - back]], 1)
    u = jax.nn.silu(conv + lp.get("ssm_conv_bias", 0.0))
    rbc = u @ lp["ssm_x"]
    dt = jax.nn.softplus(
        _rms(rbc[..., :r], lp["ssm_dt_norm"], cfg.norm_eps) @ lp["ssm_dt"]
        + lp["ssm_dt_bias"])
    y = jamba_selective_scan(
        u, dt, -jnp.exp(lp["ssm_a_log"]),
        _rms(rbc[..., r:r + n], lp["ssm_b_norm"], cfg.norm_eps),
        _rms(rbc[..., r + n:], lp["ssm_c_norm"], cfg.norm_eps),
        lp["ssm_d"])
    return (y * jax.nn.silu(z)) @ lp["ssm_out"]


def _jamba_layer(params, cfg, l):
    """Layer ``l``'s parameters out of the program's tree, float32: the
    attention layers are one stack, ``layers``, and each RUN of
    consecutive mamba layers a stack of its own: ``mamba_layers``, then
    ``mamba_1_layers`` and so on."""
    types = cfg.layer_types

    def stack(i):
        if types[i] != "mamba":
            return "layers"
        run = sum(types[j] == "mamba" and (j == 0 or types[j - 1] != "mamba")
                  for j in range(i + 1)) - 1
        return f"mamba_{run}_layers" if run else "mamba_layers"

    at = sum(stack(i) == stack(l) for i in range(l))
    return jax.tree.map(lambda w: w[at].astype(F32), params[stack(l)])


def jamba_forward(params, tokens, cfg):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above). ``params`` is the program's tree, any storage dtype."""
    hd = cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    embed = params["embed"].astype(F32)
    with jax.default_matmul_precision("highest"):
        x = embed[tokens]
        for l in range(cfg.n_layers):
            lp = _jamba_layer(params, cfg, l)
            if cfg.layer_types[l] == "mamba":
                x = x + jamba_mamba_mixer(
                    _rms(x, lp["ssm_norm"], cfg.norm_eps), lp, cfg)
            else:
                h = _rms(x, lp["attn_norm"], cfg.norm_eps)
                q = (h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd)
                k = (h @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, hd)
                v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
                k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
                x = x + a @ lp["wo"]
            h = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return x @ (embed.T if cfg.tie_embeddings
                    else params["lm_head"].astype(F32))


def jamba_loss(params, batch, cfg, vocab_rows=None):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one); no aux term. ``vocab_rows``: the loss over
    the first that many rows of the vocabulary, the other logits
    removed. ``jax.grad`` of this is the reference gradient; the tied
    matrix's is the sum of its two uses."""
    logits = jamba_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` nemotron_h): Hugging
# Face's modeling_nemotron_h.py; Mamba-2 (Dao & Gu, arXiv 2405.21060);
# the router and the multi-token-prediction glue of DeepSeek-V3 (arXiv
# 2412.19437, sections 2.1.2 and 2.2).
#
# Decoder, pre-norm, residual stream ``x`` [B, T, D], no position
# encoding anywhere. A layer has ONE part, named by ``layer_types``
# (``hybrid_override_pattern``: M, *, E): ``x = x + part(RMSNorm(x))``.
#
# - ``mamba2`` (``H = ssd_heads`` heads of ``P = ssd_head_dim``
#   channels, ``N = ssd_state`` states, ``G = ssd_groups`` groups: head
#   ``h`` reads group ``h // (H / G)``), a sequence at a time:
#   1. ``[z, xBC, r] = h W_in`` (``ssd_in``, columns ``[z | xBC | dt]``,
#      ``H P``, ``H P + 2 G N`` and ``H`` wide), no bias;
#   2. ``xBC = SiLU(conv(xBC) + b_conv)``: depthwise, causal,
#      ``conv_kernel`` taps (``ssd_conv``, ``ssd_conv_bias``);
#      ``[X, B, C] = xBC``, ``H P``, ``G N``, ``G N`` wide;
#   3. ``dt = softplus(r + dt_bias)`` (no clamp), ``A = -exp(A_log)`` a
#      head, float32;
#   4. a head's state ``S`` [P, N], ``S_0 = 0``; for each token:
#      ``S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t``;
#      ``Y_t = S_t C_t + D X_t``;
#   5. ``y = GroupRMSNorm(Y * SiLU(z))``: the gate FIRST, then an RMSNorm
#      over each group's ``H P / G`` channels, gain ``ssd_out_norm``;
#   6. ``y W_out`` (``ssd_out``), no bias;
# - ``full_attention``: ``n_heads`` query heads on ``n_kv_heads``
#   key/value heads (32 on 2), causal ``softmax(q k / sqrt(head_dim))
#   v``, ``W_o``. No RoPE, no bias, no gate, no q/k norm;
# - ``experts``: ``s = sigmoid(u W_r)``; the K experts with the largest
#   ``s + expert_bias`` (``n_group`` 1: no group limit), weights
#   ``route_scale * s / (sum of the chosen s + 1e-20)``; ``v = u
#   W_lat_down``; ``r = sum over the chosen e of w_e relu(v W1_e)^2
#   W2_e``; out ``= r W_lat_up + relu(u Ws1)^2 Ws2``: the router and the
#   shared expert read the ``D``-wide ``u``, only the routed experts live
#   in the latent space; no auxiliary term;
# - ``logits = RMS_final(x) W_head`` (untied); MTP, one module, on the
#   stream ``x`` BEFORE the final norm: ``m_t = [RMS_e(embed[token_{t+1}])
#   ; RMS_h(x_t)] W_eh``; the module's layers (``mtp_types``) on ``m``;
#   ``logits2 = RMS_mtp(m) W_head``; ``loss = CE(logits, token_{t+1}) +
#   mtp_weight * CE(logits2, token_{t+2})``, the second over the
#   positions that have a ``token_{t+2}``.
#
# Departures: parameters stored in bf16 are read as float32;
# ``expert_bias`` is data; ``num_logits_to_keep``, ``use_mamba_kernels``
# and ``moe_shared_expert_overlap`` are runtime keys and change no
# result; no clamp on ``dt`` (``time_step_limit`` is no key of the
# published config); where the latent projections stand, the glue's
# order and ``mtp_weight`` are the family's, not keys (the
# configuration's file lists them under ``assumed``). Step 4 is a
# ``lax.scan`` over TOKENS exactly as written: no chunk, no kernel,
# nothing of ops/ssd.py.
# ---------------------------------------------------------------------

def nemotronh_ssd(X, dt, A, Bm, Cm, D):
    """Step 4 for ``X`` [B, T, H, P], ``dt`` [B, T, H], ``A``, ``D``
    [H], ``Bm``, ``Cm`` [B, T, G, N], float32 -> ``Y`` [B, T, H, P]."""
    b, _, heads, p = X.shape
    rep = heads // Bm.shape[2]

    def token(S, x):
        X, dt, Bt, Ct = x               # [B, H, P], [B, H], [B, G, N]
        Bt, Ct = jnp.repeat(Bt, rep, 1), jnp.repeat(Ct, rep, 1)
        S = jnp.exp(dt * A)[..., None, None] * S \
            + (dt[..., None] * X)[..., None] * Bt[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct) + D[:, None] * X

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, p, Bm.shape[-1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (X, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def nemotronh_mamba2_mixer(h, lp, cfg):
    """The mamba2 mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters (steps 1-6 above)."""
    b, t, _ = h.shape
    H, G, N = cfg.ssd_heads, cfg.ssd_groups, cfg.ssd_state
    di, gn = H * cfg.ssd_head_dim, G * N
    zxr = h @ lp["ssd_in"]
    z, xbc, r = zxr[..., :di], zxr[..., di:2 * di + 2 * gn], \
        zxr[..., 2 * di + 2 * gn:]
    taps, conv = lp["ssd_conv"].shape[0], jnp.zeros_like(xbc)
    for j in range(taps):
        back = taps - 1 - j                  # xBC as it was ``back`` ago
        conv = conv + lp["ssd_conv"][j] * jnp.concatenate(
            [jnp.zeros((b, back, xbc.shape[-1]), F32), xbc[:, :t - back]],
            1)
    xbc = jax.nn.silu(conv + lp.get("ssd_conv_bias", 0.0))
    y = nemotronh_ssd(
        xbc[..., :di].reshape(b, t, H, -1),
        jax.nn.softplus(r + lp["ssd_dt_bias"]), -jnp.exp(lp["ssd_a_log"]),
        xbc[..., di:di + gn].reshape(b, t, G, N),
        xbc[..., di + gn:].reshape(b, t, G, N), lp["ssd_d"])
    return _gated_group_norm(y.reshape(b, t, di), z, lp["ssd_out_norm"], G,
                             cfg.norm_eps) @ lp["ssd_out"]


def _gated_group_norm(y, z, gain, groups, eps):
    """Step 5: the gate FIRST, then the RMSNorm over each group."""
    y = (y * jax.nn.silu(z)).reshape(*y.shape[:-1], groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(z.shape) * gain


def _relu2_act(x):
    return jnp.square(jax.nn.relu(x))


def _relu2(h, up, down):
    return _relu2_act(h @ up) @ down


def nemotronh_expert_layer(h, lp, cfg):
    """One expert layer on normalized ``h`` [B, T, D], in two parts:
    ``(Shared(h), the routed sum over the experts ``lp`` holds, after the
    up-projection out of the latent space)``. ``lp`` is one layer's
    float32 parameters; its expert matrices hold experts ``first_expert
    .. + n_experts_held - 1`` (all, where no share is set). The routed
    part is linear in the experts: over all the shares it adds up to the
    uncut layer's."""
    first = cfg.first_expert
    held = cfg.n_experts_held or cfg.n_experts
    w = afmoe_route(h, lp, cfg)[..., first:first + held]
    v = h @ lp["moe_lat_down"]
    act = _relu2_act(jnp.einsum("btl,elf->btef", v, lp["moe_up"]))
    y = jnp.einsum("btef,efl->btel", act, lp["moe_down"])
    return (_relu2(h, lp["shared_up"], lp["shared_down"]),
            jnp.einsum("bte,btel->btl", w, y) @ lp["moe_lat_up"])


def _nemotronh_layers(stacks, types, x, cfg):
    """The layers ``types`` on the stream ``x``, their parameters found
    in ``stacks`` by their own count (``layers``, ``mamba2_layers``,
    ``expert_layers``: a kind's layers in order)."""
    hd = cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t, _ = x.shape
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    names = {"mamba2": "mamba2_layers", "full_attention": "layers",
             "experts": "expert_layers"}
    for l, kind in enumerate(types):
        at = sum(k == kind for k in types[:l])
        lp = jax.tree.map(lambda w: w[at].astype(F32), stacks[names[kind]])
        if kind == "mamba2":
            x = x + nemotronh_mamba2_mixer(
                _rms(x, lp["ssd_norm"], cfg.norm_eps), lp, cfg)
        elif kind == "experts":
            x = x + sum(nemotronh_expert_layer(
                _rms(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg))
        else:
            h = _rms(x, lp["attn_norm"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd)
            k = (h @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
            x = x + a @ lp["wo"]
    return x


def nemotronh_forward(params, tokens, cfg, mtp_targets=None):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above); with ``mtp_targets`` [B, T] (token ``t+1`` a position) ->
    (logits, the MTP module's logits). ``params`` is the program's tree,
    any storage dtype."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32)
        x = _nemotronh_layers(params, cfg.layer_types, embed[tokens], cfg)
        logits = _rms(x, params["final_norm"].astype(F32),
                      cfg.norm_eps) @ head
        if mtp_targets is None:
            return logits
        mp = jax.tree.map(lambda w: w.astype(F32), params["mtp"])
        m = jnp.concatenate(
            [_rms(embed[mtp_targets], mp["token_norm"], cfg.norm_eps),
             _rms(x, mp["hidden_norm"], cfg.norm_eps)], -1) @ mp["eh_proj"]
        m = _nemotronh_layers(mp, cfg.mtp_types, m, cfg)
        return logits, _rms(m, mp["final_norm"], cfg.norm_eps) @ head


def _token_after(targets):
    """``targets`` holds token ``t+1`` a position: -> token ``t+2``."""
    return jnp.roll(targets, -1, 1)


def nemotronh_loss(params, batch, cfg, vocab_rows=None, terms=False):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one) plus, where ``cfg.mtp_layers``,
    ``cfg.mtp_weight`` times the MTP module's against the token after
    the target, over the positions that have one; no aux term.
    ``vocab_rows``: the loss over the first that many rows of the
    vocabulary. ``terms``: -> (the main term, the MTP term unweighted).
    ``jax.grad`` of this is the reference gradient."""
    def ce(logits, targets, mask):
        logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return jnp.sum(nll * mask) / jnp.sum(mask)

    targets = batch["targets"]
    mask = batch.get("mask", jnp.ones(targets.shape, F32)).astype(F32)
    if not cfg.mtp_layers:
        main = ce(nemotronh_forward(params, batch["tokens"], cfg), targets,
                  mask)
        return (main, 0.0) if terms else main
    logits, logits2 = nemotronh_forward(params, batch["tokens"], cfg,
                                        targets)
    has_next = (jnp.arange(targets.shape[1]) < targets.shape[1] - 1
                ).astype(F32)
    main = ce(logits, targets, mask)
    mtp = ce(logits2, _token_after(targets), mask * has_next)
    return (main, mtp) if terms else main + cfg.mtp_weight * mtp


# ---------------------------------------------------------------------
# MiniCPM-SALA (``model_type`` minicpm_sala; openbmb/MiniCPM-SALA's
# config.json): InfLLM-V2 block-sparse attention (arXiv 2509.24663;
# MiniCPM4, arXiv 2506.07900) beside Lightning linear attention (arXiv
# 2401.04658; MiniMax-01, arXiv 2501.08313), under MiniCPM's muP
# scalings.
#
# Decoder, pre-norm, residual stream ``x`` [B, T, D], no bias anywhere,
# ``L`` the PUBLISHED depth (``lightning_depth``), ``r = residual_mult``
# (``scale_depth / sqrt(L)``):
#
# - ``x_0 = embed_mult * E[token]`` (``scale_emb``); a layer: ``x <- x +
#   r * mixer(RMSNorm_1(x))``, ``x <- x + r * FFN(RMSNorm_2(x))``,
#   ``FFN(h) = (silu(h W_g) * (h W_u)) W_d``; logits ``=
#   (RMSNorm_f(x) / logit_div) W_head`` (``hidden_size /
#   dim_model_base``), untied;
# - ``lightning_attention`` (published layer ``l``, head ``n`` of ``H``,
#   ``d`` wide): ``q_t = RoPE_t(RMSNorm_q(h_t W_q)[n])``, ``k_t``
#   likewise (the norm over each head's ``d``, one gain shared by the
#   heads; half-split RoPE over the whole head), ``v_t = (h_t W_v)[n]``;
#   ``S_0 = 0``, ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t
#   / sqrt(d)`` (no denominator); ``lambda = exp(-s_n f_l)``, ``s_n =
#   2^(-8 (n + 1) / H)``, ``f_l = 1 - l / (L - 1) + 1e-5``; ``y =
#   (RMSNorm_o(concat_n o_t) * sigmoid(h_t W_z)) W_o``;
# - ``sparse_attention`` (``n_heads`` query heads on ``n_kv_heads``
#   key/value heads): ``q``, ``k`` under the same norm a head, NO RoPE;
#   a token ``t`` and group ``g`` choose blocks by steps 1-5 of
#   ``ops/sparse_attention.py``'s description (written out again in
#   ``sala_selection`` below, from the text and not from that code);
#   ONE softmax a query head over the keys ``s <= t`` of the chosen
#   blocks, scores ``q . k / sqrt(d)``; ``y = (concat_n o_t * sigmoid(h_t
#   W_z)) W_o``. A sequence of up to ``sparse_dense_len`` tokens runs
#   the layer dense (plain causal attention).
#
# Departures, each a size or a form the published config.json has no key
# for (the benchmark's configuration file lists them under ``assumed``):
# the selection's sizes and rules (block 64, top 64, pooling 32 / 16,
# one initial and 32 local blocks counted INSIDE the 64, a window is
# admitted when it lies wholly at or before the token, ties to the lower
# index) follow MiniCPM4's ``sparse_config``; step 2's normaliser is
# exact where the family's inference code approximates it from keys
# pooled four times coarser; the decay's form is Lightning Attention's
# slopes with MiniMax-01's layer factor; ``mup_denominator`` scales the
# family's learning rates and initialisations and enters no forward;
# parameters stored in bf16 are read as float32. The recurrence is a
# ``lax.scan`` over TOKENS exactly as written (no chunk, nothing of
# ops/ssd.py), the sparse layer an explicit [T, T] mask (no block is
# gathered, nothing of ops/sparse_attention.py).
# ---------------------------------------------------------------------

def sala_rates(cfg, layer):
    """``-s_n f_l`` [H] of the published layer ``layer``."""
    H, L = cfg.lightning_heads, cfg.lightning_depth or cfg.n_layers
    n = jnp.arange(H, dtype=F32)
    return -(2.0 ** (-8.0 * (n + 1.0) / H)) \
        * (1.0 - layer / max(L - 1, 1) + 1e-5)


def sala_recurrence(q, k, v, rates):
    """``S_t = exp(rate) S_{t-1} + k_t v_t^T; o_t = S_t^T q_t`` from
    ``S_0 = 0``, token by token: ``q``, ``k``, ``v`` [B, T, H, d]
    float32, ``rates`` [H] -> [B, T, H, d]."""
    def token(S, x):
        qt, kt, vt = x                                        # [B, H, d]
        S = jnp.exp(rates)[:, None, None] * S \
            + kt[..., :, None] * vt[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    b, _, h, d = q.shape
    _, o = jax.lax.scan(token, jnp.zeros((b, h, d, d), F32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 1)


def _half_split_rope(x, theta):
    """``x`` [B, T, H, d] turned by its position, pairs ``(i, i + d/2)``."""
    t, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs        # [T, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def sala_lightning_mixer(h, lp, cfg, layer):
    """The lightning mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters, ``layer`` its published index."""
    b, t, _ = h.shape
    H, d = cfg.lightning_heads, cfg.lightning_head_dim

    def heads(w):
        return (h @ lp[w]).reshape(b, t, H, d)

    q = _half_split_rope(_rms(heads("wq"), lp["q_norm"], cfg.norm_eps),
                         cfg.rope_theta)
    k = _half_split_rope(_rms(heads("wk"), lp["k_norm"], cfg.norm_eps),
                         cfg.rope_theta)
    o = sala_recurrence(q, k, heads("wv"), sala_rates(cfg, layer)) \
        / d ** 0.5
    y = _rms(o.reshape(b, t, H * d), lp["out_norm"], cfg.norm_eps)
    return (y * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]


def sala_selection(q, k, cfg):
    """``Sel(t, g)`` as a mask over blocks, bool [B, T, G, T / block],
    for ``q`` [B, T, H, d], ``k`` [B, T, G, d] float32 (after their
    norms), by the five steps, every intermediate whole:

    1. ``kbar_j = mean(k[stride j : stride j + kernel])``, ``j = 0 .. (T
       - kernel) / stride``;
    2. ``p[t, n, j] = softmax_j(q[t, n] . kbar_j / sqrt(d))`` over the
       ``j`` with ``stride j + kernel - 1 <= t``;
    3. ``P[t, g, j]`` = the sum of ``p`` over the group's heads;
    4. block ``b`` scores the max of ``P[t, g, j]`` over ``j`` in ``[r b
       - 1, r b + r - 1]``, ``r = block / stride``, among the ``j`` that
       exist and step 2 admits, -inf where none; blocks with ``block b >
       t`` excluded;
    5. the first ``init_blocks`` and the last ``window_blocks`` begun
       blocks forced; then the best scoring others, one arg-max at a
       time (the lower index on a tie), until ``topk`` are chosen or no
       begun block is left."""
    b, t, heads, d = q.shape
    groups = k.shape[2]
    block, stride, kernel = cfg.sparse_block, cfg.sparse_stride, \
        cfg.sparse_kernel
    nj, nb, r = (t - kernel) // stride + 1, t // block, block // stride
    kbar = jnp.stack([jnp.mean(k[:, stride * j:stride * j + kernel], 1)
                      for j in range(nj)], 1)                # [B, J, G, d]
    at = jnp.arange(t)
    admitted = (stride * jnp.arange(nj) + kernel - 1)[None, :] \
        <= at[:, None]                                       # [T, J]
    s = jnp.einsum("btgnd,bjgd->btgnj",
                   q.reshape(b, t, groups, heads // groups, d), kbar) \
        / d ** 0.5
    s = jnp.where(admitted[:, None, None, :], s, -jnp.inf)
    p = jnp.where(admitted[:, None, None, :],
                  jnp.exp(s - jax.nn.logsumexp(
                      jnp.where(admitted[:, None, None, :], s, -1e30), -1,
                      keepdims=True)), 0.0)
    P = jnp.where(admitted[:, None, :], jnp.sum(p, 3), -jnp.inf)
    score = jnp.stack([
        jnp.max(P[..., max(r * blk - 1, 0):min(r * blk + r, nj)], -1)
        for blk in range(nb)], -1)                           # [B, T, G, nb]
    blk = jnp.arange(nb)
    own = (at // block)[:, None]
    begun = (blk <= own)[None, :, None, :]
    forced = begun & ((blk < cfg.sparse_init_blocks)
                      | (blk > own - cfg.sparse_window_blocks)
                      )[None, :, None, :]
    sel = jnp.broadcast_to(forced, score.shape)
    for _ in range(cfg.sparse_topk):
        room = jnp.sum(sel, -1, keepdims=True) < cfg.sparse_topk
        left = jnp.where(begun & ~sel, score, -jnp.inf)
        best = jax.nn.one_hot(jnp.argmax(left, -1), nb, dtype=bool)
        sel = sel | (best & room & jnp.isfinite(
            jnp.max(left, -1, keepdims=True)))
    return sel


def sala_sparse_mixer(h, lp, cfg, dense=False):
    """The sparse mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters; ``dense``: every earlier key (what a sequence of
    up to ``sparse_dense_len`` tokens runs) -> (what it adds, the
    selection [B, T, G, blocks] or None)."""
    b, t, _ = h.shape
    hd, groups = cfg.head_dim, cfg.n_kv_heads
    rep = cfg.n_heads // groups
    q = _rms((h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd), lp["q_norm"],
             cfg.norm_eps)
    k = _rms((h @ lp["wk"]).reshape(b, t, groups, hd), lp["k_norm"],
             cfg.norm_eps)
    v = (h @ lp["wv"]).reshape(b, t, groups, hd)
    mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, :, None]
    sel = None
    if not dense:
        sel = jax.lax.stop_gradient(sala_selection(q, k, cfg))
        mask = mask & jnp.repeat(sel, cfg.sparse_block, -1)  # [B, T, G, T]
    mask = jnp.repeat(jnp.moveaxis(jnp.broadcast_to(
        mask, (b, t, groups, t)), 2, 1), rep, 1)              # [B, H, T, T]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)) / hd ** 0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, rep, 2)).reshape(
        b, t, -1)
    return (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"], sel


def sala_forward(params, tokens, cfg, selections=None):
    """tokens [B, T] -> logits [B, T, vocab] f32 (the description above).
    ``params`` is the program's tree, any storage dtype; a layer's
    parameters are found by ``cfg.layer_plan()``. ``selections``: a list
    that gets each sparse layer's selection appended."""
    with jax.default_matmul_precision("highest"):
        x = cfg.embed_mult * params["embed"].astype(F32)[tokens]
        r = cfg.residual_mult
        for l, spec in enumerate(cfg.layer_plan()):
            lp = jax.tree.map(lambda w: w[spec.index].astype(F32),
                              params[spec.stack])
            h = _rms(x, lp["attn_norm"], cfg.norm_eps)
            if spec.mixer == "lightning":
                y = sala_lightning_mixer(h, lp, cfg, l)
            else:
                y, sel = sala_sparse_mixer(
                    h, lp, cfg, dense=tokens.shape[1] <= cfg.sparse_dense_len)
                if selections is not None:
                    selections.append(sel)
            x = x + r * y
            h = _rms(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + r * _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return (x / cfg.logit_div) @ params["lm_head"].astype(F32)


def sala_loss(params, batch, cfg):
    """Mean token cross-entropy over the vocabulary rows held, over the
    positions ``batch["mask"]`` keeps (all without one). ``jax.grad`` of
    this is the reference gradient."""
    logp = jax.nn.log_softmax(sala_forward(params, batch["tokens"], cfg), -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones(nll.shape, F32)).astype(F32)
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# Xing4.0-29B-A4B (``model_type`` xing4_0; XingChen-AGI/Xing4.0-29B-A4B's
# config.json). Every layer: multi-head latent attention and a
# feed-forward part (the first ``first_k_dense_replace`` a dense SwiGLU,
# the others a shared expert beside the K of E routed experts), each
# round a residual path of ``n = hc_mult`` streams mixed by
# manifold-constrained hyper-connections; one multi-token-prediction
# module. With ``h`` a part's input [D]:
#
# - attention (DeepSeek-V2, arXiv 2405.04434, section 2.1, the training
#   form): ``c_q = RMSNorm(h W_qa)``, ``[q_n | q_r] = c_q W_qb`` a head;
#   ``[c_kv | k_r] = h W_kva``, ``c_kv = RMSNorm(c_kv)``, ``[k_n | v] =
#   c_kv W_kvb`` a head; ``q = [q_n | RoPE(q_r)]``, ``k = [k_n |
#   RoPE(k_r)]``, ONE ``k_r`` for all heads; ``o = softmax(q k^T s,
#   causal) v`` with ``s = m^2 / sqrt(d_qk)``, ``m = 0.1 mscale_all_dim
#   ln(factor) + 1``; ``out = o W_o``. RoPE's frequencies are YaRN's
#   (Hugging Face's DeepSeek-V3 form): ``theta^(-2i/d)`` and the same
#   over ``factor`` blended by a linear ramp between the correction
#   dimensions of ``beta_fast`` and ``beta_slow`` turns in the original
#   length; cos and sin times ``m(mscale) / m(mscale_all_dim)``;
# - the residual path (mHC, arXiv 2512.24880) round a part ``F`` (with
#   its pre-norm), a token's streams ``X`` in R^{n x D}, ``x~ =
#   vec(X) / rms(vec(X))``: ``H~_pre = a_pre (x~ Phi_pre) + b_pre``,
#   ``H~_post`` alike, ``H~_res = a_res mat(x~ Phi_res) + b_res``;
#   ``H_pre = sigmoid(H~_pre)``, ``H_post = 2 sigmoid(H~_post)``,
#   ``H_res`` = ``hc_sinkhorn_iters`` times {rows over (their sum +
#   hc_eps); columns over (theirs + hc_eps)} of ``exp(clamp(H~_res))``;
#   ``u = sum_i H_pre[i] X[i]``, ``X'[i] = sum_j H_res[i, j] X[j] +
#   H_post[i] F(u)``. In: ``n`` copies of the embedding; out: their sum;
# - experts (DeepSeek-V3, arXiv 2412.19437, section 2.1): ``s =
#   sigmoid(h W_r)`` over all E; the K largest by ``s + bias``; weights
#   the chosen ``s`` over their sum, times ``routed_scaling_factor``;
#   ``Shared(h) + sum_k w_k Expert_k(h)``, every one a SwiGLU;
# - MTP (DeepSeek-V3, section 2.2, one module): ``[RMSNorm(E[t+1]) ;
#   RMSNorm(stream)] W_eh`` through one expert layer of its own (from
#   ``n`` copies to their sum, as the model), a final norm of its own,
#   the model's head, against token ``t+2``, weight ``mtp_weight``;
#   ``stream`` is the SUM of the model's streams before its final norm.
#
# Written as the sections above: float32 at "highest" matmul precision,
# an explicit mask a head, the Sinkhorn loop a Python loop, the experts
# a loop over the ones held, the streams ``[B, T, n, D]`` as the
# equations have them (the program carries ``[B, n, T, D]``); it reads
# the program's tree (``hc_<part>_phi`` [n, D, n (n + 2)] is ``[Phi_pre
# | Phi_post | Phi_res]`` with its rows a stream) and the
# ``LlamaConfig`` as data. Departures from the published description,
# which gives the residual path's four sizes and no more: everything of
# it above but those sizes is this repo's reading of the paper (where
# the clamp and ``hc_eps`` stand, the norm without a gain, ``n`` copies
# in and a sum out); the norms on the two latents, the half-split
# pairing of the rotated dimensions, the YaRN formulas and ``m^2`` in
# the scale are DeepSeek-V3's as Hugging Face has them;
# ``e_score_correction_bias`` is read as data (zero, untrained), with no
# auxiliary term and no group limit (``n_group`` 1); the MTP module's
# form, its weight and how it meets the streams are assumed. A share of
# the experts (``n_experts_held``) computes the shared expert and the
# routed sum over the experts held.
# ---------------------------------------------------------------------

def xing4_yarn(cfg):
    """-> (inverse frequencies [d_r / 2], what cos and sin are
    multiplied by, the softmax scale), from ``cfg.rope_yarn = (factor,
    original length, beta_fast, beta_slow, mscale, mscale_all_dim)``."""
    import math

    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    factor, original, fast, slow, mscale, all_dim = cfg.rope_yarn

    def correction(turns):   # the dimension that turns ``turns`` times
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), d - 1)
    if low == high:
        high += 0.001
    inv = []
    for i in range(d // 2):
        plain = base ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(plain / factor * ramp + plain * (1.0 - ramp))
    scale = (cfg.qk_nope_head_dim + d) ** -0.5
    if all_dim:
        scale *= m(all_dim) ** 2
    return jnp.asarray(inv, F32), m(mscale) / m(all_dim), scale


def xing4_attention(h, lp, cfg):
    """Latent attention on normalized ``h`` [B, T, D] with one layer's
    float32 parameters ``lp``: a masked softmax a head."""
    b, t, _ = h.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    inv, mult, scale = xing4_yarn(cfg)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv              # [T, dr/2]
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult

    def rope(x):    # [B, T, dr], half-split
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    c_q = _rms(h @ lp["wq_a"], lp["q_a_norm"], cfg.norm_eps)
    q = (c_q @ lp["wq_b"]).reshape(b, t, H, dn + dr)
    ckv = h @ lp["wkv_a"]
    c_kv = _rms(ckv[..., :cfg.kv_lora_rank], lp["kv_a_norm"], cfg.norm_eps)
    k_r = rope(ckv[..., cfg.kv_lora_rank:])          # one for all heads
    kv = (c_kv @ lp["wkv_b"]).reshape(b, t, H, dn + cfg.v_head_dim)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    heads = []
    for n in range(H):
        qn = jnp.concatenate([q[:, :, n, :dn], rope(q[:, :, n, dn:])], -1)
        kn = jnp.concatenate([kv[:, :, n, :dn], k_r], -1)
        s = jnp.einsum("bqd,bkd->bqk", qn, kn) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        heads.append(jnp.einsum("bqk,bkd->bqd", p, kv[:, :, n, dn:]))
    return jnp.concatenate(heads, -1) @ lp["wo"]


def xing4_sinkhorn(logits, cfg):
    """``logits`` [..., n, n] -> ``exp(clamp(logits))`` after
    ``hc_sinkhorn_iters`` iterations of {rows over (their sum + hc_eps);
    columns over (theirs + hc_eps)}."""
    m = jnp.exp(jnp.clip(logits, cfg.hc_clamp[0], cfg.hc_clamp[1]))
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + cfg.hc_eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + cfg.hc_eps)
    return m


def xing4_hc_coefficients(X, lp, part, cfg):
    """A token's streams ``X`` [..., n, D] -> (``H_pre`` [..., n],
    ``H_post`` [..., n], ``H_res`` [..., n, n]) of the part ``part``
    ("attn" or "mlp") of the layer ``lp``."""
    n = cfg.hc_mult
    flat = X.reshape(*X.shape[:-2], -1)
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg.norm_eps)
    proj = x @ lp[f"hc_{part}_phi"].reshape(flat.shape[-1], n * (n + 2))
    a, bias = lp[f"hc_{part}_alpha"], lp[f"hc_{part}_bias"]
    pre = a[0] * proj[..., :n] + bias[:n]
    post = a[1] * proj[..., n:2 * n] + bias[n:2 * n]
    res = (a[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(
        *proj.shape[:-1], n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            xing4_sinkhorn(res, cfg))


def xing4_hyper_connection(X, lp, part, cfg, F):
    """``X`` [B, T, n, D] -> ``X'``: the part ``F`` ([B, T, D] -> [B, T,
    D]) round the streams."""
    pre, post, res = xing4_hc_coefficients(X, lp, part, cfg)
    y = F(jnp.einsum("bti,btid->btd", pre, X))
    return jnp.einsum("btij,btjd->btid", res, X) \
        + post[..., None] * y[:, :, None, :]


def xing4_expert_layer(h, lp, cfg):
    """The FFN of one expert layer on normalized ``h`` [B, T, D], in two
    parts: ``(Shared(h), the routed sum over the experts ``lp`` holds)``,
    an expert at a time. The routed part is linear in the experts: over
    all the shares it adds up to the uncut layer's."""
    first = cfg.first_expert
    w = afmoe_route(h, lp, cfg)
    routed = jnp.zeros_like(h)
    for e in range(cfg.n_experts_held or cfg.n_experts):
        routed = routed + w[..., first + e, None] * _swiglu(
            h, lp["moe_gate"][e], lp["moe_up"][e], lp["moe_down"][e])
    return (_swiglu(h, lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"]), routed)


def _xing4_layers(stacks, dense, experts, x, cfg):
    """``dense`` leading dense layers (``stacks["dense_layers"]``) and
    ``experts`` expert layers (``stacks["layers"]``) on ``x`` [B, T, D]:
    ``hc_mult`` copies in, their sum out."""
    X = jnp.repeat(x[:, :, None, :], cfg.hc_mult, 2)
    for l in range(dense + experts):
        stack, at = ("dense_layers", l) if l < dense \
            else ("layers", l - dense)
        lp = jax.tree.map(lambda w: w[at].astype(F32), stacks[stack])
        X = xing4_hyper_connection(
            X, lp, "attn", cfg, lambda u: xing4_attention(
                _rms(u, lp["attn_norm"], cfg.norm_eps), lp, cfg))

        def ffn(u):
            h = _rms(u, lp["mlp_norm"], cfg.norm_eps)
            if l < dense:
                return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            return sum(xing4_expert_layer(h, lp, cfg))

        X = xing4_hyper_connection(X, lp, "mlp", cfg, ffn)
    return jnp.sum(X, 2)


def xing4_forward(params, tokens, cfg, mtp_targets=None):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above); with ``mtp_targets`` [B, T] (token ``t+1`` a position) ->
    (logits, the MTP module's logits). ``params`` is the program's tree,
    any storage dtype."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32)
        x = _xing4_layers(params, cfg.n_dense_layers,
                          cfg.n_layers - cfg.n_dense_layers, embed[tokens],
                          cfg)
        logits = _rms(x, params["final_norm"].astype(F32),
                      cfg.norm_eps) @ head
        if mtp_targets is None:
            return logits
        mp = jax.tree.map(lambda w: w.astype(F32), params["mtp"])
        m = jnp.concatenate(
            [_rms(embed[mtp_targets], mp["token_norm"], cfg.norm_eps),
             _rms(x, mp["hidden_norm"], cfg.norm_eps)], -1) @ mp["eh_proj"]
        m = _xing4_layers(mp, 0, len(cfg.mtp_types), m, cfg)
        return logits, _rms(m, mp["final_norm"], cfg.norm_eps) @ head


def xing4_loss(params, batch, cfg, vocab_rows=None, terms=False):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one) plus ``cfg.mtp_weight`` times the MTP
    module's against the token after the target, over the positions
    that have one; no aux term. ``vocab_rows``, ``terms``: as
    :func:`nemotronh_loss`. ``jax.grad`` of this is the reference
    gradient."""
    def ce(logits, targets, mask):
        logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return jnp.sum(nll * mask) / jnp.sum(mask)

    targets = batch["targets"]
    mask = batch.get("mask", jnp.ones(targets.shape, F32)).astype(F32)
    logits, logits2 = xing4_forward(params, batch["tokens"], cfg, targets)
    has_next = (jnp.arange(targets.shape[1]) < targets.shape[1] - 1
                ).astype(F32)
    main = ce(logits, targets, mask)
    mtp = ce(logits2, _token_after(targets), mask * has_next)
    return (main, mtp) if terms else main + cfg.mtp_weight * mtp


# ---------------------------------------------------------------------
# Olmo-Hybrid-7B (Allen AI, ``model_type`` ``olmo_hybrid``), from its
# published ``config.json`` and the Olmo family's published block. No
# bias anywhere, eps ``rms_norm_eps``. A layer norms each part's OUTPUT
# and nothing else (OLMo 2's reordered norm, arXiv:2501.00656 section
# 3): ``x = x + RMS(Mixer(x))``, then ``x = x + RMS(SwiGLU(x))``, plain
# gains (the program's leaves ``post_attn_norm``, ``post_mlp_norm``; no
# ``attn_norm``, ``gdn_norm`` or ``mlp_norm`` exists). Every layer's FFN
# is a dense SwiGLU.
#
# - a ``linear_attention`` layer's mixer, Gated DeltaNet as
#   flash-linear-attention's ``GatedDeltaNet`` and Qwen3-Next's steps
#   1-6 above have it (``Hk`` = ``Hv`` = 30 heads, ``dk`` 96, ``dv``
#   192: keys and values of TWO widths), with ONE line changed
#   (``linear_allow_neg_eigval``, arXiv:2411.12537): step 3's write
#   strength is ``beta = 2 sigmoid(b)``, so that with unit keys the
#   write's transition ``I - beta k k^T`` has its eigenvalue along ``k``
#   in (-1, 1);
# - a ``full_attention`` layer's: ``q = RMS(h W_q)``, ``k = RMS(h
#   W_k)``, each over its WHOLE projected width before the split into
#   heads (the family's QK-norm), ``v = h W_v``; NO position encoding
#   (``rope_theta`` null: the recurrences order the tokens); causal
#   ``softmax(q k / sqrt(head_dim)) v``, as many key/value heads as
#   query heads; ``a W_o``;
# - ``logits = RMS_final(x) W_head``, untied; loss = mean token
#   cross-entropy.
#
# The share: as for afmoe above (``vocab_rows``). What the published
# ``config.json`` has no key for (where the norms sit, the q/k norm, the
# mixer's internals) is listed with its source under ``assumed`` in
# ``chipbench/configs/olmo-hybrid-7b.json``. The recurrence is a
# ``lax.scan`` over TOKENS exactly as written
# (:func:`qwen3next_delta_rule`, which takes any ``dk``, ``dv`` and
# ``beta``): no chunks, no WY form, nothing of
# ops/gated_delta_rule.py or ops/gdn_chain.py.
# ---------------------------------------------------------------------

def olmohybrid_gated_delta_net(h, lp, cfg):
    """The ``linear_attention`` mixer on the stream ``h`` [B, T, D] (no
    norm before it) with one layer's float32 parameters: Qwen3-Next's
    steps 1-6 with ``beta = cfg.linear_beta_max * sigmoid(b)``."""
    return qwen3next_gated_delta_net(h, lp, cfg, cfg.linear_beta_max)


def olmohybrid_forward(params, tokens, cfg):
    """tokens [B, T] -> logits [B, T, vocab] f32 (see the description
    above). ``params`` is the program's tree, any storage dtype."""
    hd, rep = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        for l in range(cfg.n_layers):
            lp = _qwen3next_layer(params, cfg, l)
            if cfg.layer_types[l] == "linear_attention":
                mixed = olmohybrid_gated_delta_net(x, lp, cfg)
            else:
                q = _rms(x @ lp["wq"], lp["q_norm"], cfg.norm_eps)
                k = _rms(x @ lp["wk"], lp["k_norm"], cfg.norm_eps)
                q = q.reshape(b, t, cfg.n_heads, hd)
                k = jnp.repeat(k.reshape(b, t, cfg.n_kv_heads, hd), rep, 2)
                v = jnp.repeat((x @ lp["wv"]).reshape(
                    b, t, cfg.n_kv_heads, hd), rep, 2)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                mixed = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(
                    b, t, -1) @ lp["wo"]
            x = x + _rms(mixed, lp["post_attn_norm"], cfg.norm_eps)
            x = x + _rms(_swiglu(x, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]),
                         lp["post_mlp_norm"], cfg.norm_eps)
        x = _rms(x, params["final_norm"].astype(F32), cfg.norm_eps)
        return x @ params["lm_head"].astype(F32)


def olmohybrid_loss(params, batch, cfg, vocab_rows=None):
    """Mean token cross-entropy over the positions ``batch["mask"]``
    keeps (all without one); no aux term. ``vocab_rows``: as
    :func:`qwen3next_loss`. ``jax.grad`` of this is the reference
    gradient."""
    logits = olmohybrid_forward(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(logits[..., :vocab_rows], -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------
# Ouro-2.6B (ByteDance, ``model_type`` ``ouro``; "Scaling Latent
# Reasoning via Looped Language Models", arXiv:2510.25741, section 3;
# ``modeling_ouro.py`` beside the published ``config.json``): a LoopLM.
# ONE stack of ``L`` layers, run ``R = total_ut_steps`` times with the
# same weights; no bias anywhere, eps ``rms_norm_eps``, ``N(.; g)`` an
# RMSNorm with gain ``g``.
#
# - ``h^(0) = E[x]``;
# - a trip ``t = 1..R``: ``u_0 = h^(t-1)``; for each layer ``l``, four
#   norms: ``a = u + N(Attn_l(N(u; g1_l)); g2_l)``, ``u' = a +
#   N(SwiGLU_l(N(a; g3_l)); g4_l)`` (the program's leaves ``attn_norm``,
#   ``post_attn_norm``, ``mlp_norm``, ``post_mlp_norm``); then ``h^(t) =
#   N(u_L; g_f)``: the final norm closes EVERY trip, and its output is
#   both what the trip's exit reads and what the next trip starts from;
# - ``Attn``: ``q, k, v = z W_q, z W_k, z W_v``, as many key/value heads
#   as query heads, half-split RoPE over the whole head (the Llama
#   pairing), causal ``softmax(q k / sqrt(head_dim)) v``, ``W_o``;
# - an exit a trip: logits ``z^(t) = h^(t) W_head`` (ONE head, untied);
#   gate ``s_t = h^(t) . w_g + b_g``, ``lambda_t = sigmoid(s_t)``, a
#   number a token;
# - the exit distribution a token: ``p_t = lambda_t prod_{j<t} (1 -
#   lambda_j)`` for ``t < R``, ``p_R = prod_{j<R} (1 - lambda_j)``: the
#   last trip takes what is left, ``lambda_R`` enters nothing;
# - the loss (the paper's stage-I objective, joint: nothing detached):
#   ``mean_i [ sum_t p_{t,i} CE(z_i^(t), y_i) - beta H(p_{.,i}) ]``,
#   ``H(p) = - sum_t p_t log p_t``, ``beta`` the program's
#   ``exit_entropy_weight``.
#
# What the published ``config.json`` has no key for (the four norms and
# their order, the norm inside the loop, the gate's form and start,
# ``beta``) is listed with its source under ``assumed`` in
# ``chipbench/configs/ouro-2.6b.json``; if the published
# ``modeling_ouro.py`` or the paper departs from a line above, the
# published form wins. Departures known: ``early_exit_threshold`` (1:
# every trip is run) is an inference setting and is not read; the
# paper's later stages lower ``beta`` and its stage II trains the gate
# alone against a detached improvement signal: not here.
#
# Trips and layers are Python loops; nothing of ``models/llama.py``'s
# scan, blocks or kernels. ``trip_layers``: a stack of layers a TRIP
# (``R`` copies of the shared stack, each visit reading its own), so
# that ``jax.grad`` gives the gradient of each visit apart: their sum is
# what a shared leaf's gradient has to be.
# ---------------------------------------------------------------------

def ouro_forward(params, tokens, cfg, trip_layers=None):
    """tokens [B, T] -> (logits [R, B, T, vocab], gate logits [R, B, T]),
    float32, an entry an exit. ``params`` is the program's tree, any
    storage dtype."""
    hd = cfg.head_dim
    b, t = tokens.shape

    def rope(x):
        return _half_split_rope(x, cfg.rope_theta)

    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.tree.map(lambda w: w.astype(F32), params)
    logits, gates = [], []
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        for trip in range(cfg.loop_steps):
            stack = p["layers"] if trip_layers is None else jax.tree.map(
                lambda w: w.astype(F32), trip_layers[trip])
            for l in range(cfg.n_layers):
                lp = jax.tree.map(lambda w: w[l], stack)
                z = _rms(h, lp["attn_norm"], cfg.norm_eps)
                q = rope((z @ lp["wq"]).reshape(b, t, cfg.n_heads, hd))
                k = rope((z @ lp["wk"]).reshape(b, t, cfg.n_heads, hd))
                v = (z @ lp["wv"]).reshape(b, t, cfg.n_heads, hd)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                att = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                mixed = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(
                    b, t, -1) @ lp["wo"]
                h = h + _rms(mixed, lp["post_attn_norm"], cfg.norm_eps)
                z = _rms(h, lp["mlp_norm"], cfg.norm_eps)
                h = h + _rms(_swiglu(z, lp["w_gate"], lp["w_up"],
                                     lp["w_down"]),
                             lp["post_mlp_norm"], cfg.norm_eps)
            h = _rms(h, p["final_norm"], cfg.norm_eps)
            logits.append(h @ p["lm_head"])
            gates.append(h @ p["exit_gate_w"] + p["exit_gate_b"][0])
    return jnp.stack(logits), jnp.stack(gates)


def ouro_exit_distribution(gates):
    """Gate logits [R, ...] -> the exit distribution [R, ...]: ``p_t =
    sigmoid(s_t) prod_{j<t} sigmoid(-s_j)``, the last exit what is
    left."""
    lam = jax.nn.sigmoid(gates)
    left, p = jnp.ones_like(lam[0]), []
    for t in range(gates.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def ouro_loss(params, batch, cfg, trip_layers=None, terms=False):
    """The expected exit loss less ``cfg.exit_entropy_weight`` times the
    exit distribution's entropy, the mean over the positions
    ``batch["mask"]`` keeps (all without one). ``terms``: also its
    parts, (the mean cross-entropy of each exit [R], the mean exit
    distribution [R], the mean entropy). ``jax.grad`` of this is the
    reference gradient."""
    logits, gates = ouro_forward(params, batch["tokens"], cfg, trip_layers)
    targets = jnp.broadcast_to(batch["targets"], gates.shape)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               targets[..., None], -1)[..., 0]
    p = ouro_exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    mask = batch.get("mask", jnp.ones_like(entropy))

    def mean(x):
        return jnp.sum(x * mask, (-2, -1)) / jnp.sum(mask)

    loss = mean(jnp.sum(p * nll, 0) - cfg.exit_entropy_weight * entropy)
    return (loss, (mean(nll), mean(p), mean(entropy))) if terms else loss
