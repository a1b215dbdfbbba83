"""KV-cached autoregressive decoding for the llama family.

Net-new vs the reference (Horovod ships no inference path); TPU-first:
one jitted program — prefill fills the cache with a single full-sequence
pass, then ``lax.scan`` decodes token-by-token against a static-shaped
cache (no dynamic shapes, no per-step retrace). The per-step attention
is GQA-native (``_decode_attention``): the fused kernel reads the cache
at its stored kv-head width, and slots past the current position mask
themselves by global index.

Dense and MoE configs (per-token top-k routing is sequence-independent,
so cached decode routes each new token exactly as a full forward would).
With the default ``moe_impl="auto"`` the single-chip prefill resolves
to the DROPLESS grouped dispatch (ops/grouped_moe.py), which matches
the top-k decode path exactly — no capacity drops anywhere. A
checkpoint trained under an expert-parallel mesh (auto -> GShard,
capacity drops) should set ``moe_impl="gshard"`` for bit-parity with
its training-time prefill semantics; its decode steps still use the
drop-free top-k path (a single token never overflows capacity).
Single-device or data-parallel batch — the sequence axis is not
sharded at decode.

Numerics (changed round 5): decode attention — both the fused pallas
kernel and the einsum fallback — casts the softmaxed attention
probabilities to bf16 before the PV contraction and accumulates in
f32, matching the training flash kernel's recipe exactly. Round 4
kept the probabilities f32 through PV; rounding them to bf16 can flip
the greedy argmax when two next-token logits sit within rounding
distance, so greedy output may differ from round-4 behavior at such
near-ties. The two decode paths stay mutually consistent, and
train/decode now share one numerics contract (see docs/benchmarks.md,
"Decode numerics").
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.llama import _ffn as _llama_ffn
from horovod_tpu.models.llama import (
    _project_qkv,
    _rmsnorm,
    _rope,
    moe_route,
)


def require_decodable(c):
    """Decode and serving implement the uniform decoder (dense or
    routed FFN, whole-width q/k-norm). The fields of the newer
    architectures (a window, a layer pattern, a share of the experts,
    an output gate, ...) are training only until the cache and this
    module have them: refuse, never ignore one."""
    fields = c.training_only_fields()
    if c.kv_lora_rank:
        raise ValueError(
            f"LlamaConfig fields {fields}: latent attention is training "
            "only. Decode lacks a cache of the latent and the shared "
            "rotated key (serving/kvcache.py holds k and v a head) and "
            "the absorbed form of the up-projections "
            "(ops/decode_attention.py takes one width for q, k and v)")
    if c.hc_mult:
        raise ValueError(
            f"LlamaConfig fields {fields}: hyper-connections are training "
            "only. Prefill and cached decode carry ONE residual stream "
            "[B, T, D] from layer to layer, not hc_mult of them")
    if c.post_norm == "only":
        raise ValueError(
            f"LlamaConfig fields {fields}: output-only norms are training "
            "only. Prefill and the cached step norm each part's INPUT "
            "with attn_norm and mlp_norm, leaves this tree does not "
            "have, and pass no part's output through post_attn_norm or "
            "post_mlp_norm")
    if c.linear_beta_max != 1.0:
        raise ValueError(
            f"LlamaConfig fields {fields}: a linear_attention layer's "
            "write strength is training only. Decode lacks the layer "
            "itself: a cache of its recurrent state (a [key_dim, "
            "value_dim] matrix a head beside the KV cache's keys and "
            "values, and the last conv_taps - 1 projected tokens) and "
            "the delta rule's one-token step that would read beta")
    if fields:
        raise ValueError(
            f"LlamaConfig fields {fields} are training only "
            "(llama_forward / llama_loss): prefill, cached decode and "
            "serving do not implement them yet")


def _ffn(h, lp, c):
    """llama.py's shared FFN, aux loss dropped (decode does not train).
    Serves prefill, dense decode, and MoE decode at large batch;
    small-batch MoE decode uses _moe_ffn_topk. Dispatch follows
    ``c.moe_impl`` exactly as llama_forward with no mesh does (see the
    module docstring for the gshard-trained-checkpoint caveat)."""
    y, _aux = _llama_ffn(h, lp, c, None)
    return y


def _moe_ffn_topk(h, lp, c):
    """Decode-step MoE FFN: gather only the K routed experts' weights
    per token and run a [K]-grouped matmul — FLOPs and weight-HBM reads
    scale with top-k, not the expert count E (the capacity dispatch in
    llama._moe_ffn streams all E experts, which is right for training
    but E/K-times wasteful for a single decoded token). Routing (same
    router, same gate normalization) matches llama._moe_ffn; a single
    token can never overflow per-expert capacity, so no drop divergence.
    ``c.norm_topk_prob`` is honoured through the same moe_route.

    The gathers materialize one [K,D,F]-sized weight copy per token, so
    this path only wins while B*T*K < E — _decode_ffn falls back to the
    streaming dispatch beyond that (where it reads fewer weight bytes
    anyway).
    """
    dt = c.compute_dtype
    K = c.n_experts_per_token
    gate_vals, gate_idx, _aux = moe_route(h, lp["router"], K,
                                          c.norm_topk_prob)    # [B,T,K]
    wg = lp["moe_gate"].astype(dt)[gate_idx]                # [B,T,K,D,F]
    wu = lp["moe_up"].astype(dt)[gate_idx]
    wd = lp["moe_down"].astype(dt)[gate_idx]                # [B,T,K,F,D]
    hk = h.astype(dt)
    gate = jax.nn.silu(jnp.einsum("btd,btkdf->btkf", hk, wg))
    up = jnp.einsum("btd,btkdf->btkf", hk, wu)
    y = jnp.einsum("btkf,btkfd->btkd", gate * up, wd)
    return jnp.einsum("btk,btkd->btd", gate_vals.astype(dt), y)


def _decode_ffn(h, lp, c):
    """FFN for the one-token decode step: dense as-is; MoE via the
    top-k gather while it touches fewer weights than streaming all E
    experts (shapes are static, so this is a trace-time choice)."""
    if c.n_experts > 0:
        b, t, _ = h.shape
        if b * t * c.n_experts_per_token < c.n_experts:
            return _moe_ffn_topk(h, lp, c)
    return _ffn(h, lp, c)


def _layer_qkv(h, lp, c, positions):
    """Project h [B,T,D] (normalized) -> rope'd q, rope'd k, and v for
    one layer, through llama.py's own projection (q/k norms included
    where the config has them), so decode cannot drift from training.
    RoPE turns the whole head: ``require_decodable`` refuses a
    configuration with ``partial_rotary``."""
    q, k, v = _project_qkv(h, lp, c)
    return (_rope(q, positions, c.rope_theta),
            _rope(k, positions, c.rope_theta), v)


def _decode_attention(q, cache_k, cache_v, pos):
    """One-token attention against the cache, GQA-native: the fused
    pallas kernel on TPU (scores + masked softmax + PV folded into the
    one pass that streams the cache — ops/decode_attention.py), the
    same-recipe einsum chain elsewhere. Either way kv-heads are indexed
    directly: repeating the cache to H query heads would stream an
    n_rep× expanded copy through HBM per layer per step, and decode is
    pure bandwidth (at batch 64 that repeat alone tripled step time).
    """
    from horovod_tpu.ops.decode_attention import decode_attention

    return decode_attention(q, cache_k, cache_v, pos)


def _attend_step(x, lp, c, cache_k, cache_v, li, pos):
    """One decode-position layer step against the STACKED caches.

    x [B,D]; cache_k/v [L,B,Hkv,max_len,hd] with positions < pos
    valid; this step's k/v are written at (li, :, pos) before
    attending. The caches stay scan CARRIES and are updated by
    layer-indexed dynamic_update_slice — passing them as scanned
    xs/stacked ys instead forces XLA to rebuild the whole stacked
    buffer every token (measured: a 2x176 MB copy per decode step at
    flagship b64, ~25% of the step's bandwidth budget).
    Returns (x_out, cache_k, cache_v).
    """
    dt = c.compute_dtype
    b = x.shape[0]
    # x is 2-D [B, D] through the layer: the [B, 1, D] singleton-dim
    # form makes XLA pick {2,0,1}-style layouts for the residual/norm
    # chains and pay a layout cast per op (~2 ms/step across 14 layers
    # at flagship b64). The sequence dim reappears only at the
    # attention/FFN boundaries that need it.
    positions = jnp.broadcast_to(pos, (b, 1))
    h = _rmsnorm(x, lp["attn_norm"].astype(dt), c.norm_eps)
    q, k_new, v_new = _layer_qkv(h[:, None, :], lp, c, positions)
    # Caches live heads-major [L, B, Hkv, S, D] (the attention-kernel
    # layout); the new token's [B, 1, Hkv, D] projects to [B, Hkv, 1, D].
    cache_k = lax.dynamic_update_slice(
        cache_k, k_new.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, v_new.transpose(0, 2, 1, 3)[None], (li, 0, 0, pos, 0))
    ck = lax.dynamic_index_in_dim(cache_k, li, 0, keepdims=False)
    cv = lax.dynamic_index_in_dim(cache_v, li, 0, keepdims=False)
    attn = _decode_attention(q, ck, cv, pos)
    x = x + attn.reshape(b, -1) @ lp["wo"].astype(dt)
    h = _rmsnorm(x, lp["mlp_norm"].astype(dt), c.norm_eps)
    x = x + _decode_ffn(h[:, None, :], lp, c)[:, 0, :]
    return x, cache_k, cache_v


def _prefill(params, prompt, c, pad_to):
    """One full-sequence pass capturing each layer's K/V.

    Returns (x [B, T, D] final hidden states, cache_k, cache_v
    [L, B, Hkv, T+pad_to, hd] heads-major). The shared front half of
    :func:`llama_generate` (pad_to=max_new_tokens, decode scans in
    place) and :func:`llama_prefill` (the serving lane, pad_to=0 — the
    paged KV pool owns the growth instead of padding)."""
    dt = c.compute_dtype
    b, t0 = prompt.shape
    x = params["embed"].astype(dt)[prompt]
    positions = jnp.broadcast_to(jnp.arange(t0), (b, t0))

    def prefill_layer(x, lp):
        h = _rmsnorm(x, lp["attn_norm"].astype(dt), c.norm_eps)
        q, k, v = _layer_qkv(h, lp, c, positions)
        # Flash kernel (not blockwise): a long prompt must not
        # materialize the [B,H,T,T] score tensor.
        from horovod_tpu.ops import flash_attention

        attn = flash_attention(q, k, v, causal=True)
        x = x + attn.reshape(b, t0, -1) @ lp["wo"].astype(dt)
        h = _rmsnorm(x, lp["mlp_norm"].astype(dt), c.norm_eps)
        x = x + _ffn(h, lp, c)
        # Cache padded to max_len so decode's dynamic_update_slice fits.
        # Heads-major cache layout [B, Hkv, max_len, hd] (the decode
        # attention kernel's layout); one transpose per layer at
        # prefill, never again.
        pad = jnp.zeros((b, c.n_kv_heads, pad_to, c.head_dim), dt)
        return x, (jnp.concatenate([k.transpose(0, 2, 1, 3), pad], 2),
                   jnp.concatenate([v.transpose(0, 2, 1, 3), pad], 2))

    x, (cache_k, cache_v) = lax.scan(prefill_layer, x, params["layers"])
    return x, cache_k, cache_v


def _lm_logits(params, x_last, c):
    """Final-norm + lm_head in f32 (x_last [..., D])."""
    dt = c.compute_dtype
    h = _rmsnorm(x_last, params["final_norm"].astype(dt), c.norm_eps)
    return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("config", "pad_to"))
def llama_prefill(params, prompt, config, pad_to=0):
    """Serving-lane prefill: one compiled pass -> the greedy first
    token plus this prompt's per-layer K/V for a paged cache.

    prompt [B, T] int32 -> (first [B] int32, cache_k, cache_v
    [L, B, Hkv, T+pad_to, hd]). Unlike :func:`llama_generate` the
    caches come back UNPADDED by default — the continuous-batching
    engine writes them into fixed-size pool blocks (per-sequence block
    tables), so sequence growth never re-allocates a monolithic
    buffer. Greedy only: the serving lane's elastic re-queue guarantee
    is token-identity, which sampling would break."""
    require_decodable(config)
    x, cache_k, cache_v = _prefill(params, prompt, config, pad_to)
    logits = _lm_logits(params, x[:, -1:, :], config)[:, 0, :]
    return (jnp.argmax(logits, axis=-1).astype(prompt.dtype),
            cache_k, cache_v)


@partial(jax.jit, static_argnames=("config",))
def llama_decode_step(params, tokens, cache_k, cache_v, lengths, config,
                      k_scale=None, v_scale=None):
    """One continuous-batching decode step over a RAGGED batch.

    Each batch row b holds its own sequence at position ``lengths[b]``
    (valid cached slots < lengths[b]; pool-gathered caches are padded
    to one static S — the mask, not the shape, carries raggedness, so
    one compiled program serves every batch composition). tokens [B]
    int32 (each row's last emitted token); cache_k/v
    [L, B, Hkv, S, hd] — f32/bf16, or int8 with per-slot dequant
    scales ``k_scale``/``v_scale`` [L, B, Hkv, S] (the paged pool's
    per-block scales expanded; dequant is f32-accumulate inside
    ``decode_attention_ragged``).

    Returns (next [B] int32 greedy tokens, k_new, v_new
    [L, B, Hkv, hd] — this step's projections, which the CALLER writes
    into the paged cache; the step never updates the cache in place,
    so the gathered view can stay a cheap scan input instead of a
    carried copy).
    """
    from horovod_tpu.ops.decode_attention import decode_attention_ragged

    c = config
    require_decodable(c)
    dt = c.compute_dtype
    b = tokens.shape[0]
    x = params["embed"].astype(dt)[tokens]          # [B, D]
    positions = jnp.asarray(lengths, jnp.int32)[:, None]  # [B, 1]

    def layer(x, xs):
        lp, ck, cv, ks, vs = xs
        h = _rmsnorm(x, lp["attn_norm"].astype(dt), c.norm_eps)
        q, k_new, v_new = _layer_qkv(h[:, None, :], lp, c, positions)
        attn = decode_attention_ragged(
            q, ck, cv, lengths,
            k_new.transpose(0, 2, 1, 3), v_new.transpose(0, 2, 1, 3),
            k_scale=ks, v_scale=vs)
        x = x + attn.reshape(b, -1) @ lp["wo"].astype(dt)
        h = _rmsnorm(x, lp["mlp_norm"].astype(dt), c.norm_eps)
        x = x + _decode_ffn(h[:, None, :], lp, c)[:, 0, :]
        return x, (k_new[:, 0, :, :], v_new[:, 0, :, :])

    # Caches (and scales) are read-only here, so they ride as scanned
    # xs — no carried copy (the _attend_step rebuild hazard only bites
    # when the scan must WRITE the stacked buffer). Absent scales scan
    # as zero-width placeholders so both modes share one layer body.
    if k_scale is None:
        empty = jnp.zeros((c.n_layers, 0), jnp.float32)

        def layer_noscale(x, xs):
            lp, ck, cv, _, _ = xs
            return layer(x, (lp, ck, cv, None, None))

        x, (k_new, v_new) = lax.scan(
            layer_noscale, x,
            (params["layers"], cache_k, cache_v, empty, empty))
    else:
        x, (k_new, v_new) = lax.scan(
            layer, x,
            (params["layers"], cache_k, cache_v, k_scale, v_scale))
    logits = _lm_logits(params, x, c)               # [B, V]
    nxt = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    return nxt, k_new, v_new


@partial(jax.jit,
         static_argnames=("config", "max_new_tokens", "temperature"))
def llama_generate(params, prompt, config, max_new_tokens,
                   temperature=0.0, key=None):
    """Greedy (temperature=0) or sampled decoding.

    prompt [B, T] int32 -> [B, T + max_new_tokens] (prompt + generated).
    The whole prefill+decode is ONE compiled program; recompiles when
    (config, prompt length, max_new_tokens, temperature) change —
    temperature is static because it selects greedy vs sampled tracing.
    """
    c = config
    require_decodable(c)
    dt = c.compute_dtype
    b, t0 = prompt.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, max_new_tokens)  # [0]=first, rest=steps

    # ---- prefill: one full pass, capturing each layer's K/V ----------
    x, cache_k, cache_v = _prefill(params, prompt, c, max_new_tokens)
    # cache_k/v: [L, B, Hkv, max_len, hd]

    def logits_of(x_last):
        return _lm_logits(params, x_last, c)

    def pick(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        return jax.random.categorical(
            k, logits / temperature, axis=-1).astype(prompt.dtype)

    first = pick(logits_of(x[:, -1:, :])[:, 0, :], keys[0])  # [B]

    # ---- decode: scan max_new_tokens-1 steps (each feeds the previous
    # token and emits the NEXT one; 'first' is prepended at the end) ---
    def step(carry, step_key):
        token, pos, cache_k, cache_v = carry
        x = params["embed"].astype(dt)[token]       # [B, D] (2-D!)

        def layer(lcarry, lp):
            x, ck, cv, li = lcarry
            x, ck, cv = _attend_step(x, lp, c, ck, cv, li, pos)
            return (x, ck, cv, li + 1), None

        (x, cache_k, cache_v, _), _ = lax.scan(
            layer, (x, cache_k, cache_v, jnp.int32(0)),
            params["layers"])
        nxt = pick(logits_of(x), step_key)
        return (nxt, pos + 1, cache_k, cache_v), nxt

    (_, _, _, _), toks = lax.scan(
        step, (first, jnp.int32(t0), cache_k, cache_v), keys[1:])
    # toks [max_new_tokens-1, B]: tokens generated after 'first'.
    return jnp.concatenate(
        [prompt, first[:, None], jnp.transpose(toks, (1, 0))], axis=1)
