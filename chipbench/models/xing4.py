"""Model adapter for kind "xing4": Xing4.0-29B-A4B's decoder (every layer
multi-head latent attention, 32 heads whose queries and keys are 192 wide
beside values 128 wide, and a feed-forward part, a leading dense SwiGLU
or a shared expert beside 4 of 64 routed experts, each round a residual
path of four streams mixed by Sinkhorn-projected hyper-connections; one
multi-token-prediction module) as ONE chip of the eight that share each
layer holds it: experts 0-7 of the published 64, an eighth of the
vocabulary, every head. Run through the program's own ``LlamaConfig`` /
``llama_init`` / ``llama_loss`` with the grouped dispatch, the path every
LM kind takes; this adapter extends kind "afmoe"'s (the batch it keeps,
the share's counts). Nothing of the model is re-implemented here except
the plain float32 reference that ``correct`` is decided against: the
benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes, on the weights the run ENDED with (bounds
and the readings they were set from: below, and PERF.md section 2):

(a) the flash kernels at [batch, seq, 32, 192 / 128] with the handed-in
    scale ``m^2 / sqrt(192)`` against explicit-mask float32 attention in
    passes of 1024 query rows, forward and the gradients of ``q``, ``k``,
    ``v``;
(b) the program's logits and its MTP module's logits on the batch the
    run trained on (``llama.py``'s own stream, final norms, head and MTP
    glue, one program) against the reference a layer at a time, the
    attention in passes of query rows;
(c) at every expert layer, on the REFERENCE's input to the router, the
    program's router (``llama.route_layer`` in the compute dtype) against
    the reference's float32 scores: the share of tokens whose four
    experts are the reference's, and for every token that differs the
    margin, in the reference's own scores, between the experts swapped (a
    near-tie is allowed and counted, a swap across a margin is not);
(d) the program's ``H_res`` (``llama._hc_coefficients``) at the first
    expert layer's two parts, on the reference's streams there: every
    row and column sums to 1 within the CPU test's tolerance.

The control (``python3 -m chipbench.models.xing4 --seed N``): the same
run with the REFERENCE computed from fp8 operands and matrices put in the
program's place in (a) and (b), through the same verdicts; it has to come
out not correct in each, and in (b) by either set of logits. The same run
also reads, through (b)'s verdict on the same reference, the PROGRAM's
logits with a fault of the model planted in it (``_planted``): the scale
without ``m^2`` and ``H_post`` without its 2 have to be refused too.

NOT compared on the chip: any gradient of the new mechanisms at the timed
size (the backward of the hyper-connections, of the latent projections
and of the Sinkhorn scan, and what the remat save-list keeps). A wrong
backward leaves the logits of the weights it produced equal to the
reference's on those weights; the losses falling and the CPU tests at
float32 (tests/single/test_xing4_reference.py: every gradient leaf) are
what holds it (PERF.md section 7).
"""

import functools
import types

import jax
import jax.numpy as jnp

from chipbench import mla_counts
from chipbench.models import afmoe, lm
from chipbench.models.afmoe import (
    F32,
    FP8,
    _block,
    _fp8,
    _normal,
    _rel_errs,
    _rms,
    _swiglu,
    _through,
)

# published config.json key -> LlamaConfig field (``n_routed_experts`` is
# the experts HELD; the published count is in ``reduced``)
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
         "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
         "n_routed_experts": "n_experts_held",
         "num_experts_per_tok": "n_experts_per_token",
         "first_k_dense_replace": "n_dense_layers",
         "n_shared_experts": "n_shared_experts",
         "scoring_func": "score_func", "norm_topk_prob": "norm_topk_prob",
         "routed_scaling_factor": "route_scale",
         "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
         "qk_nope_head_dim": "qk_nope_head_dim",
         "qk_rope_head_dim": "qk_rope_head_dim",
         "v_head_dim": "v_head_dim", "hc_mult": "hc_mult",
         "hc_sinkhorn_iters": "hc_sinkhorn_iters", "hc_eps": "hc_eps",
         "num_nextn_predict_layers": "mtp_layers"}
_YARN = ("factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "mscale", "mscale_all_dim")

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 57; PERF.md section 2): the largest the PROGRAM read over
# its seeds, and what the REFERENCE reads in the program's place with its
# operands (for the logits, its matrices) rounded to fp8 (e4m3, the
# nearest precision below the configuration's bf16), which has to fail
# (``Fp8InTheProgramsPlace``).
# (a) Flash at 192 / 128, max-abs error over the largest entry: forward,
# backward (bf16 operands, ``p`` and ``ds`` rounded where they enter a
# matmul). Program 0.0039-0.0068 forward, 0.0029-0.0066 backward over
# eighteen seeds; fp8 0.118 forward, dq 0.098, dk 0.121, dv 0.050.
KERNEL_TOL = {"fwd": 2.5e-2, "bwd": 1.6e-2}
# (b) The logits and the MTP module's: the l2 norm of the difference
# over that of the reference's (a token whose choice of experts lies
# within rounding of an edge hands a row to another expert and moves its
# logits by that expert's whole output: the largest entry's error reads
# those tokens alone, 0.42-0.71 for the program and 0.75-0.77 for fp8; it
# is printed, not judged). Program 0.153-0.177 over eighteen seeds at
# five expert layers and 0.135-0.155 over eleven at the four the cell
# holds (PERF.md section 2; seeded weights under the
# scale ``m^2 / sqrt(192)`` give attention logits of deviation 2.0 over
# 8192 keys, near an arg-max: a rounding moves the winner; the same
# program without ``m^2`` reads 0.065, my CPU runs at a quarter of the
# width, PERF.md section 6); fp8 0.629 and 0.537 at five layers, 0.584
# and 0.506 at four. Planted in the program at four layers, on the chip:
# the scale without ``m^2`` 1.223 and 0.970, ``H_post`` without its 2
# 0.562 and 0.526 (both refused); nineteen Sinkhorn iterations 0.136 and
# 0.131, the program's own reading (``MUST_REFUSE``, below).
LOGITS_TOL = 0.3
# (c) The share of tokens whose four experts are the reference's, the
# worst layer, and the largest margin between two experts swapped, as a
# share of the larger score (the program's router runs its float32
# matmul in one bf16 pass on the chip). Program 0.9902-0.9954 and
# 5.9e-4 - 1.8e-3; a router that takes its ninth expert for its first
# reads a margin of tenths (tests/chipbench/test_xing4_cell.py).
ROUTE_SAME_MIN = 0.97
ROUTE_MARGIN_TOL = 6e-3
# (d) Rows and columns of ``H_res`` against 1, a token's worst row or
# column: the mean over tokens and the worst token, what twenty
# iterations leave (Sinkhorn-Knopp converges linearly, at a rate the
# token's logits set), the CPU test's tolerances
# (tests/single/test_xing4_reference.py: 1.6e-4 / 0.016 over 4096
# seed-like tokens after twenty iterations, 0.085 / 0.38 after two).
# Program on the chip 1.0e-4 - 1.7e-4 / 0.012-0.023.
HC_SUM_TOL = {"mean": 1e-3, "worst": 5e-2}
# Query rows a pass of the reference's attention.
ROW_BLOCK = 1024


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, attention as a masked softmax
# over every key, the Sinkhorn loop a Python loop, the experts a loop
# over the ones held; nothing imported from the program but the rule
# that says in which stack a layer's parameters lie
# (``LlamaConfig.layer_plan``). The equations and the departures:
# horovod_tpu/models/reference.py. So that it fits at the cell's 8192
# tokens the SAME math runs a layer at a time and the attention in
# passes of ``ROW_BLOCK`` query rows. One pass is the whole.
# ---------------------------------------------------------------------

def yarn(c):
    """-> (inverse frequencies [dr / 2], what cos and sin are multiplied
    by, the softmax scale) from ``c.rope_yarn``."""
    import math

    d, base = c.qk_rope_head_dim, c.rope_theta
    factor, original, fast, slow, mscale, all_dim = c.rope_yarn

    def correction(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), d - 1)
    high += 0.001 * (low == high)
    inv = []
    for i in range(d // 2):
        plain = base ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(plain / factor * ramp + plain * (1.0 - ramp))
    scale = (c.qk_nope_head_dim + d) ** -0.5 * (m(all_dim) ** 2
                                                if all_dim else 1.0)
    return jnp.asarray(inv, F32), m(mscale) / m(all_dim), scale


def attend(q, k, v, scale):
    """``softmax(q k^T scale, causal) v`` of float32 ``q``, ``k``
    [B, T, H, dqk], ``v`` [B, T, H, dv] under the explicit mask ``j <=
    i``, in passes of ``ROW_BLOCK`` query rows against every key (a
    pass's scores are [B, H, rows, T]: 1 GB at the cell's shape, where
    all rows at once are 8.6 GB) -> [B, T, H, dv]."""
    b, t, h, _ = q.shape
    rows = _block(t, ROW_BLOCK)

    def block(first, q, k, v):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        i = first + jnp.arange(rows)[:, None]
        p = jax.nn.softmax(
            jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(lambda r: jax.checkpoint(block)(r, q, k, v),
                      jnp.arange(0, t, rows))          # [T/rows,B,rows,H,dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def latent_attention(h, lp, c):
    b, t, _ = h.shape
    H, dn, dr = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    inv, mult, scale = yarn(c)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv              # [T, dr/2]
    cos, sin = (f(ang)[:, None, :] * mult for f in (jnp.cos, jnp.sin))

    def rope(x):    # [B, T, heads, dr], half-split
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    q = (_rms(h @ lp["wq_a"], lp["q_a_norm"], c.norm_eps)
         @ lp["wq_b"]).reshape(b, t, H, dn + dr)
    ckv = h @ lp["wkv_a"]
    kv = (_rms(ckv[..., :c.kv_lora_rank], lp["kv_a_norm"], c.norm_eps)
          @ lp["wkv_b"]).reshape(b, t, H, dn + c.v_head_dim)
    k_r = rope(ckv[..., None, c.kv_lora_rank:])         # ONE for all heads
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r, (b, t, H, dr))], -1)
    return attend(q, k, kv[..., dn:], scale).reshape(b, t, -1) @ lp["wo"]


def sinkhorn(logits, c):
    """[..., n, n] -> ``exp(clamp(logits))`` after ``hc_sinkhorn_iters``
    times {rows over (their sum + eps); columns over (theirs + eps)}."""
    m = jnp.exp(jnp.clip(logits, c.hc_clamp[0], c.hc_clamp[1]))
    for _ in range(c.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + c.hc_eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + c.hc_eps)
    return m


def hc_coefficients(X, lp, part, c):
    """``X`` [B, T, n, D] -> (H_pre [B, T, n], H_post [B, T, n], H_res
    [B, T, n, n])."""
    n = c.hc_mult
    flat = X.reshape(*X.shape[:-2], -1)
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + c.norm_eps)
    proj = x @ lp[f"hc_{part}_phi"].reshape(flat.shape[-1], n * (n + 2))
    a, bias = lp[f"hc_{part}_alpha"], lp[f"hc_{part}_bias"]
    pre = a[0] * proj[..., :n] + bias[:n]
    post = a[1] * proj[..., n:2 * n] + bias[n:2 * n]
    res = (a[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(
        *proj.shape[:-1], n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, c))


def hyper_connection(X, lp, part, c, F):
    pre, post, res = hc_coefficients(X, lp, part, c)
    y = F(jnp.einsum("bti,btid->btd", pre, X))
    return jnp.einsum("btij,btjd->btid", res, X) \
        + post[..., None] * y[:, :, None, :]


def route(h, lp, c):
    """-> (weights [..., E] over ALL experts, the scores [..., E])."""
    s = jax.nn.sigmoid(h @ lp["router"])
    left, chosen = s + lp["expert_bias"], jnp.zeros_like(s)
    for _ in range(c.n_experts_per_token):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), c.n_experts, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = chosen * s
    return c.route_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20), s


def reference_layer(lp, X, c, dense):
    """One layer on the streams ``X`` [B, T, n, D] with its float32
    parameters ``lp`` -> (X', and of an expert layer what its router
    read [B, T, D] and its scores [B, T, E]; of a dense one None
    twice)."""
    seen = [None, None]
    with jax.default_matmul_precision("highest"):
        X = hyper_connection(
            X, lp, "attn", c, lambda u: latent_attention(
                _rms(u, lp["attn_norm"], c.norm_eps), lp, c))

        def ffn(u):
            h = _rms(u, lp["mlp_norm"], c.norm_eps)
            if dense:
                return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            w, s = route(h, lp, c)
            seen[:] = h, s
            y = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
            for e in range(c.n_experts_held):
                y = y + w[..., c.first_expert + e, None] * _swiglu(
                    h, lp["moe_gate"][e], lp["moe_up"][e],
                    lp["moe_down"][e])
            return y

        X = hyper_connection(X, lp, "mlp", c, ffn)
    return (X, *seen)


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program for the dense layers and one for the
    expert layers whatever the depth."""
    def glue(mp, nxt, x):
        with jax.default_matmul_precision("highest"):
            return jnp.concatenate(
                [_rms(nxt, mp["token_norm"], c.norm_eps),
                 _rms(x, mp["hidden_norm"], c.norm_eps)], -1) \
                @ mp["eh_proj"]

    def head(gain, w, x):
        with jax.default_matmul_precision("highest"):
            return _rms(x, gain, c.norm_eps) @ w

    return types.SimpleNamespace(
        layer={dense: jax.jit(functools.partial(reference_layer, c=c,
                                                dense=dense))
               for dense in (True, False)},
        copies=jax.jit(lambda x: jnp.repeat(x[:, :, None, :], c.hc_mult,
                                            2)),
        total=jax.jit(lambda X: jnp.sum(X, 2)),
        glue=jax.jit(glue), head=jax.jit(head))


@functools.lru_cache(maxsize=None)
def _read_layer(round_to):
    """One layer's stored leaves -> as :func:`afmoe._through` reads
    them; one program a kind of layer."""
    read = _through(round_to)
    return jax.jit(lambda lp: jax.tree.map(read, lp))


def reference_logits(params, tokens, targets, c, round_to=None,
                     visit=None):
    """The reference's logits and its MTP module's on ``tokens`` with the
    next tokens ``targets``, from the program's tree ``params``, a layer
    at a time -> (logits, MTP logits) [B, T, vocab] float32.
    ``visit(name, X, h, s)`` is handed every expert layer's input
    streams, what its router read and its scores."""
    read, run = _through(round_to), _reference_programs(c)

    def sweep(stacks, plan, x, name):
        X = run.copies(x)
        for l, spec in enumerate(plan):
            lp = _read_layer(round_to)(
                {k: w[spec.index] for k, w in stacks[spec.stack].items()})
            before = X
            X, h, s = run.layer[bool(spec.dense_ffn)](lp, X)
            if visit is not None and not spec.dense_ffn:
                visit(f"{name}{l}", spec, stacks, before, h, s)
        return run.total(X)

    embed = read(params["embed"])
    head = read(params["lm_head"])
    x = sweep(params, c.layer_plan(), embed[tokens], "layer ")
    logits = run.head(read(params["final_norm"]), head, x)
    mp = params["mtp"]
    m = run.glue({k: read(mp[k]) for k in ("token_norm", "hidden_norm",
                                            "eh_proj")}, embed[targets], x)
    m = sweep(mp, c.layer_plan(mtp=True), m, "mtp layer ")
    return logits, run.head(read(mp["final_norm"]), head, m)


# ---------------------------------------------------------------------
# The comparisons: the program's side, the reference's.
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="scale")
def _program_flash(q, k, v, w, scale):
    from horovod_tpu.ops import flash_attention

    def f(q, k, v, w):   # w rides as an argument, never closed over
        out = flash_attention(q, k, v, causal=True, scale=scale)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    got, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v, w)
    return (out,) + got


@functools.partial(jax.jit, static_argnames="scale")
def reference_attention(q, k, v, w, scale):
    """Explicit-mask float32 attention (any dtype, read as float32) and
    the gradients of ``sum(out * w)`` -> (out, dq, dk, dv), float32."""
    def f(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            out = attend(q, k, v, scale)
        return jnp.sum(out * w), out

    grads, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(
        *(x.astype(F32) for x in (q, k, v, w)))
    return (out,) + grads


@functools.lru_cache(maxsize=None)
def _program_logits(c):
    """``llama.py``'s own stream, final norms, head and MTP glue as
    ``llama_loss`` composes them, less the cross-entropy -> (logits, the
    MTP module's logits)."""
    from horovod_tpu.models import llama

    def run(params, tokens, targets):
        stream, _ = llama._llama_stream(params, tokens, c)
        return (llama._head(params, llama._final_norm(params, stream, c),
                            c),
                llama._head(params, llama._mtp_hidden(
                    params, stream, targets, c, None, None), c))
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _program_router(c):
    """The program's choice of experts [B, T, K] on what the reference's
    router read, in the compute dtype."""
    from horovod_tpu.models import llama

    return jax.jit(lambda h, lp: llama.route_layer(
        h.astype(c.compute_dtype), lp, c)[1])


@functools.lru_cache(maxsize=None)
def _program_h_res(c):
    """The program's ``H_res`` [B, n, n, T] of one part on the
    reference's streams [B, T, n, D], in the compute dtype and the
    program's layout."""
    from horovod_tpu.models import llama

    return jax.jit(lambda X, phi, alpha, bias: llama._hc_coefficients(
        jnp.swapaxes(X, 1, 2).astype(c.compute_dtype), phi, alpha, bias,
        c)[2])


@jax.jit
def _l2_err(got, ref):
    return jnp.linalg.norm((got.astype(F32) - ref).ravel()) \
        / jnp.linalg.norm(ref.ravel())


@functools.partial(jax.jit, static_argnames="k_top")
def _route_verdict(got, s, bias, k_top):
    """The program's choice ``got`` [..., K] against the reference's
    scores ``s`` [..., E] -> (the share of tokens whose sets are equal,
    the largest margin over the tokens that differ: the best ``s +
    bias`` among the experts only the reference chose less the worst
    among those only the program chose, as a share of the former, the
    share of tokens that differ)."""
    E = s.shape[-1]
    ranked = s + bias
    sets = jnp.sum(jax.nn.one_hot(jax.lax.top_k(ranked, k_top)[1], E,
                                  dtype=jnp.int32), -2) > 0
    mine = jnp.sum(jax.nn.one_hot(got, E, dtype=jnp.int32), -2) > 0
    ref_only, got_only = sets & ~mine, mine & ~sets
    same = ~jnp.any(ref_only | got_only, -1)
    best = jnp.max(jnp.where(ref_only, ranked, -jnp.inf), -1)
    worst = jnp.min(jnp.where(got_only, ranked, jnp.inf), -1)
    margin = jnp.where(same, 0.0, (best - worst) / jnp.maximum(best, 1e-30))
    return jnp.mean(same.astype(F32)), jnp.max(margin)


# ---------------------------------------------------------------------

class Model(afmoe.Model):
    """Kind "afmoe"'s adapter (the kept batch, the share's rows and
    grouped-GEMM counts) with Xing4.0's configuration and share, its
    counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a, rope = config["assumed"], config["rope_scaling"]
        assert config["hidden_act"] == "silu" \
            and config["topk_method"] == "noaux_tc" \
            and config["n_group"] == config["topk_group"] == 1 \
            and config["moe_layer_freq"] == 1 and rope["type"] == "yarn" \
            and not (config["attention_bias"]
                     or config["tie_word_embeddings"]) \
            and config["num_key_value_heads"] \
            == config["num_attention_heads"], config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            n_experts=config["reduced"]["n_routed_experts"]["published"],
            first_expert=a["first_expert"],
            rope_yarn=tuple(float(rope[k]) for k in _YARN),
            hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                      float(config["mhc_h_res_clamp_max"])),
            mtp_types=("full_attention",)
            * config["num_nextn_predict_layers"],
            mtp_weight=a["mtp_weight"], loss_chunk=a["loss_chunk"],
            moe_impl="grouped", moe_aux_weight=0.0,
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on
        self.rows_held = None      # a layer: an even router's share

    # -- counts ---------------------------------------------------------

    def _layers(self):
        """(attention layers, expert layers), the MTP module's among
        them."""
        c = self.cfg
        return c.n_layers + c.mtp_layers, \
            c.n_layers - c.n_dense_layers + c.mtp_layers

    def _rows(self):
        return [self.even_share] * self._layers()[1]

    def mla_work(self):
        """(required FLOPs, required bytes) of the latent-attention cores
        of a step: ``mla_core_roofline_pct``'s numerator."""
        c = self.cfg
        shape = (self.batch_size, self.seq, c.n_heads, c.qk_head_dim,
                 c.v_head_dim, self._layers()[0])
        return (mla_counts.core_flops(*shape), mla_counts.core_bytes(
            *shape, jnp.dtype(c.compute_dtype).itemsize))

    def hc_floor_bytes(self):
        """What the hyper-connections of a step must move: for PERF.md
        to quote beside ``hc_mix_ms_per_step``."""
        c = self.cfg
        return mla_counts.hc_bytes(
            self.units_per_step, c.hc_mult, c.d_model,
            2 * self._layers()[0], jnp.dtype(c.compute_dtype).itemsize)

    def matmul_params_per_token(self):
        """Parameters that multiply ONE token: every layer's five
        latent-attention matrices and its two parts' coefficient
        projections, the dense layer's SwiGLU, an expert layer's router,
        shared expert and the share of its routed experts an even router
        hands this chip, the MTP glue, the head twice. Not the lookup,
        not the gains."""
        c = self.cfg
        d, H, n = c.d_model, c.n_heads, c.hc_mult
        attention, experts = self._layers()
        mla = d * c.q_lora_rank + c.q_lora_rank * H * c.qk_head_dim \
            + d * (c.kv_lora_rank + c.qk_rope_head_dim) \
            + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim) \
            + H * c.v_head_dim * d
        routed = c.n_experts_per_token * c.n_experts_held / c.n_experts
        return attention * (mla + 2 * n * d * n * (n + 2)) \
            + c.n_dense_layers * 3 * d * c.d_ff \
            + experts * (d * c.n_experts + 3 * d * c.shared_width
                         + routed * 3 * d * c.expert_width) \
            + c.mtp_layers * (2 * d * d + d * c.vocab_size) \
            + d * c.vocab_size

    def flops_per_unit(self):
        return 6 * self.matmul_params_per_token() \
            + self.mla_work()[0] / self.units_per_step

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """On the chip the grad program must hold the flash pair and
        megablox's grouped GEMMs, not their reference branches, and every
        other Mosaic call none: ``moe_gmm_ms_per_step`` takes each one
        without ``hvd_flash`` in its name for megablox's."""
        if not on_tpu:
            return None
        missing = [name for name in ("tpu_custom_call", "hvd_flash_fwd",
                                     "hvd_flash_bwd_fused", "@gmm", "@tgmm")
                   if name not in text]
        if missing:
            return f"grad program lowered without {missing}: a " \
                   "kernel's reference branch ran"
        return None

    def check_outputs(self, params, key, say):
        """Returns a list of faults (empty = correct); see the module
        docstring for what is compared. As kind "afmoe": everything
        compiled here stays out of the compile cache."""
        import time

        from jax.experimental.compilation_cache import compilation_cache

        began, heard = time.time(), say
        dev = jax.local_devices()[0]

        def say(**fields):   # how long the checks take, and what they hold
            heard(seconds_into_checks=round(time.time() - began, 1),
                  device_gb_in_use=round((dev.memory_stats() or {}).get(
                      "bytes_in_use", 0) / 1e9, 2), **fields)

        ks = jax.random.split(key, 2)
        tokens = jnp.asarray(self.trained_on) \
            if self.trained_on is not None \
            else lm.Model.batch(self, ks[1])["tokens"]
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return self._check_flash(ks[0], say) \
                + self._check_model(params, tokens, say)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _flash(self, q, k, v, w, scale):
        """-> (out, dq, dk, dv) of ``sum(out * w)``."""
        return _program_flash(q, k, v, w, scale)

    def _logits(self, params, tokens, targets):
        """-> (logits, the MTP module's logits)."""
        return _program_logits(self.cfg)(params, tokens, targets)

    def _planted(self, params, tokens, targets):
        """(name, (logits, the MTP module's logits)) of the program with
        a fault planted in it, for (b)'s verdict on the same reference:
        none in the cell's run (the control plants them)."""
        return ()

    def _check_flash(self, key, say):
        c = self.cfg
        qk = (self.batch_size, self.seq, c.n_heads, c.qk_head_dim)
        vo = (self.batch_size, self.seq, c.n_heads, c.v_head_dim)
        scale = float(c.yarn()[2])
        q, k, v, w = _normal(key, (qk, qk, vo, vo))
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._flash(q, k, v, w, scale),
            reference_attention(q, k, v, w, scale)))))
        flops, nbytes = self.mla_work()
        dev = jax.local_devices()[0]
        say(event="flash_vs_explicit_mask", shape=list(qk),
            value_width=c.v_head_dim, scale=scale,
            block_rows=_block(self.seq, ROW_BLOCK), err=err,
            tol=KERNEL_TOL, required_flops_per_step=flops,
            required_bytes_per_step=nbytes,
            floor_ms=mla_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None,
            hc_floor_bytes_per_step=self.hc_floor_bytes())
        return [f"flash {name} error {e} vs the explicit mask"
                for name, e in err.items()
                if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]]

    def _check_model(self, params, tokens, say):
        """(b), (c) and (d) in one sweep of the reference."""
        c = self.cfg
        targets = jnp.roll(tokens, -1, 1)
        got = self._logits(params, tokens, targets)
        routes, sums = {}, {}

        def visit(name, spec, stacks, X, h, s):
            lp = {k: w[spec.index] for k, w in stacks[spec.stack].items()}
            same, margin = _route_verdict(
                _program_router(c)(h, lp), s, lp["expert_bias"],
                c.n_experts_per_token)
            routes[name] = {"same_share": float(same),
                            "worst_margin": float(margin)}
            if not sums:       # the first expert layer
                for part in ("attn", "mlp"):
                    res = _program_h_res(c)(
                        X, *(lp[f"hc_{part}_{leaf}"]
                             for leaf in ("phi", "alpha", "bias")))
                    off = jnp.maximum(jnp.abs(res.sum(1) - 1.0).max(1),
                                      jnp.abs(res.sum(2) - 1.0).max(1))
                    sums[part] = {"mean": float(off.mean()),
                                  "worst": float(off.max())}

        ref = reference_logits(params, tokens, targets, c, visit=visit)
        names = ("logits", "mtp_logits")
        err = {name: float(_l2_err(g, r))
               for name, g, r in zip(names, got, ref)}
        planted = {fault: {name: float(_l2_err(g, r))
                           for name, g, r in zip(names, logits, ref)}
                   for fault, logits in self._planted(params, tokens,
                                                      targets)}
        say(event="model_vs_reference", tokens=int(tokens.size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            largest_entry_err=[float(e) for e in _rel_errs(got, ref)],
            routes=routes,
            h_res_sums_off_one=sums,
            tol={"logits": LOGITS_TOL,
                 "routes_same_share_min": ROUTE_SAME_MIN,
                 "routes_margin": ROUTE_MARGIN_TOL,
                 "h_res_sums": HC_SUM_TOL},
            **({"planted": planted} if planted else {}))
        return [f"{name} error {e} vs the float32 reference"
                for name, e in err.items() if not e <= LOGITS_TOL] \
            + [f"planted {fault}: {name} error {e} vs the float32 reference"
               for fault, errs in planted.items()
               for name, e in errs.items() if not e <= LOGITS_TOL] \
            + [f"router of {name}: only {r['same_share']} of the tokens "
               "chose the reference's experts" for name, r in routes.items()
               if not r["same_share"] >= ROUTE_SAME_MIN] \
            + [f"router of {name}: two experts swapped across a margin of "
               f"{r['worst_margin']} of the reference's score"
               for name, r in routes.items()
               if not r["worst_margin"] <= ROUTE_MARGIN_TOL] \
            + [f"H_res of the first expert layer's {part} part: a row or "
               f"column sums {off} off 1" for part, off in sums.items()
               if not all(off[stat] <= tol
                          for stat, tol in HC_SUM_TOL.items())]


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what (a) and (b)
    read in the program's place is the float32 REFERENCE with its
    operands and matrices rounded to fp8 (e4m3), through the same
    verdicts. Each bound has to refuse it."""

    def _flash(self, q, k, v, w, scale):
        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, scale)

    def _logits(self, params, tokens, targets):
        return reference_logits(params, tokens, targets, self.cfg,
                                round_to=FP8)

    def _planted(self, params, tokens, targets):
        """The PROGRAM's logits (bf16, the cell's own forward) with a
        fault of the model planted in it, one at a time."""
        import dataclasses

        from horovod_tpu.models import llama

        c = self.cfg
        build = _program_logits.__wrapped__    # a program a fault
        yield "19 Sinkhorn iterations", build(dataclasses.replace(
            c, hc_sinkhorn_iters=c.hc_sinkhorn_iters - 1))(
            params, tokens, targets)
        # (mscale and mscale_all_dim 0: m = 1, cos and sin times 1 still)
        yield "the scale without m^2", build(dataclasses.replace(
            c, rope_yarn=c.rope_yarn[:4] + (0.0, 0.0)))(
            params, tokens, targets)
        was, llama._HC_POST_SCALE = llama._HC_POST_SCALE, 1.0
        try:      # read when the program is traced, at its first call
            yield "H_post without its 2", build(c)(params, tokens, targets)
        finally:
            llama._HC_POST_SCALE = was


COMPARISONS = ("flash", "logits", "mtp_logits")
# The planted faults (b)'s limit has to refuse on the chip, in the
# model's logits AND the MTP module's. Not among them: one Sinkhorn
# iteration of twenty (it moves ``H_res`` by what the twentieth leaves
# off 1, 1e-4 of a coefficient, under the bf16 program's own rounding: no
# limit above the program's readings can see it; it is read and printed,
# and held at float32 by tests/single/test_xing4_reference.py).
MUST_REFUSE = ("the scale without m^2", "H_post without its 2")


def main(argv=None):
    """The control on the chip: the cell's run, two seconds of window,
    with ``Fp8InTheProgramsPlace``. Exits 0 when every comparison came
    out NOT correct for fp8 and each fault of ``MUST_REFUSE`` was
    refused in both sets of logits; 1 when one of them passed."""
    import argparse
    import json
    import time

    t0 = time.time()
    from chipbench import child
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = child.find_cell("xing4.spmd.b1s8192")
    enable_compile_cache()
    lane = child.load_file("lanes", traffic["lane"]).Lane(traffic)
    lane.start()

    def say(**fields):
        print(json.dumps(fields), flush=True)

    result = child.measure(
        Fp8InTheProgramsPlace(config, traffic), lane, traffic,
        seed=args.seed, seconds=2.0, trace=False, t0=t0, say=say)
    refused = {kind: [f for f in result["faults"] if f.startswith(kind)]
               for kind in COMPARISONS}
    planted = {fault: [f for f in result["faults"]
                       if f.startswith(f"planted {fault}: ")]
               for fault in MUST_REFUSE}
    said = [f for fs in (*refused.values(), *planted.values()) for f in fs]
    say(event="control", fp8_refused_by=refused,
        planted_refused_by=planted,
        other_faults=[f for f in result["faults"] if f not in said])
    lane.close()
    return 0 if all(refused.values()) \
        and all(len(fs) == len(COMPARISONS[1:])
                for fs in planted.values()) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
