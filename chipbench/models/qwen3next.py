"""Model adapter for kind "qwen3next": Qwen3-Next-80B-A3B's decoder
(Gated DeltaNet layers three to one beside gated full-attention layers,
every layer an expert layer with softmax routing and a GATED shared
expert, an untied head) as ONE chip of the sixteen that share each layer
holds it: experts ``first_expert .. + num_experts - 1`` of the published
512, a slice of the vocabulary, every head of both mixers. Run through
the program's own ``LlamaConfig`` / ``llama_init`` / ``llama_loss`` with
the grouped dispatch, the path kinds "lm", "olmoe", "afmoe" and
"lfm2moe" take; this adapter extends kind "afmoe"'s (the share's counts,
the batch it keeps, the timed programs run once more, the kernel
comparisons) and borrows the blocked pieces of its reference. Nothing of
the model is re-implemented here except the plain float32 reference that
``correct`` is decided against: the benchmark's own copy (the program
keeps one in ``horovod_tpu/models/reference.py``, which a later PR may
edit; this one it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape (heads 256 wide, 16 on
   2) against an explicit-mask float32 attention computed in blocks of
   query rows, forward and gradients;
2. the grouped GEMM at the cell's shapes (expert width 512, 32 groups,
   the share's row bound, uneven groups over about half of it), forward
   and both backward directions, against float32 ``numpy`` matmuls on
   whole groups (kind "afmoe"'s comparison);
3. the program's gated delta rule (``ops/gated_delta_rule.py``, chunked)
   at [batch, seq, 32 value heads, 128] against the recurrence TOKEN BY
   TOKEN in float32, forward and the gradients of ``q``, ``k``, ``v``,
   ``g`` and ``beta``;
4. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a layer at a time and in blocks (the recurrence a
   sequence at a time, attention by query rows, the experts and the head
   by token blocks): the loss; EVERY gradient leaf (l2); and the norm of
   every leaf's change under the reference's own first Adam step.

Printed and not judged (``expert_load``): the rows the router hands the
experts held here, a layer, beside an even router's share.

The control (``python3 -m chipbench.models.qwen3next --seed N``): the
same run with the REFERENCE computed in fp8 put in the program's place
in all four comparisons, through the same verdicts; it has to come out
not correct in each.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts, gdn_counts
from chipbench.models import afmoe, lm
from chipbench.models.afmoe import (
    F32,
    FP8,
    _attend,
    _block,
    _fp8,
    _head_loss,
    _leaves_readings,
    _normal,
    _over_blocks,
    _rel_errs,
    _rms,
    _swiglu,
    _through,
    _unstack,
    adam_first_step,
    check_grouped_mm,
    reference_attention,
)

# published config.json key -> LlamaConfig field (``num_experts`` is the
# experts HELD; the published count is in ``reduced``)
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
         "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
         "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
         "num_experts": "n_experts_held",
         "num_experts_per_tok": "n_experts_per_token",
         "norm_topk_prob": "norm_topk_prob",
         "linear_conv_kernel_dim": "conv_taps",
         "linear_num_key_heads": "linear_key_heads",
         "linear_num_value_heads": "linear_value_heads",
         "linear_key_head_dim": "linear_key_dim",
         "linear_value_head_dim": "linear_value_dim"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 38; PERF.md section 2): the largest the PROGRAM read
# over eleven runs on eleven seeds (eight of the first form, the mixer
# under one checkpoint, three of the two-stage form: the same readings),
# and what the REFERENCE reads in the program's place with its matrices
# (for the kernels, its operands) rounded to fp8 (e4m3, the nearest
# precision below the configuration's bf16), which has to fail
# (``Fp8InTheProgramsPlace``; one seed).
# Flash at heads 256 wide, bf16 operands, max-abs error over the largest
# entry; forward, backward. Program 0.0027-0.0034 / 0.0025-0.0051; fp8
# 0.056 / dq 0.050, dk 0.044, dv 0.023.
KERNEL_TOL = {"fwd": 1.5e-2, "bwd": 1.5e-2}
# The gated delta rule, the same statistic: out, dq, dk, dv, dg, dbeta.
# Program 0.0037-0.0086; fp8 0.070 (dq) - 0.125 (dk).
RULE_TOL = 2.5e-2
# The grouped GEMM at width 512 reads what kind "afmoe"'s reads at 1024
# (program 0.0026-0.0036, fp8 0.037-0.047): its bound, 0.01, serves
# (``afmoe.GMM_TOL``, inside ``check_grouped_mm``).
# The step. Loss: program 1.6e-6 - 5.7e-5; fp8 6.3e-3: told apart here.
LOSS_TOL = 3e-4
# A gradient leaf's l2 error, the worst layer. Program: ``wo``, ``wv``
# 0.012-0.019, ``final_norm``, the head, ``attn_norm`` 0.018-0.031, q/k
# gains 0.030-0.045, every other matrix and gain 0.037-0.082
# (``gdn_conv`` the largest); fp8 0.229 (``final_norm``), 0.24-0.29
# (``wo``, ``wv``, ``attn_norm``, the head), 0.38-0.67 everywhere
# else.
GRAD_TOL = 0.15
# The leaves the ROUTING reaches have a bound of their own: a token
# whose choice of ten among 512 lies within bf16's rounding of an edge
# hands a whole row to another expert. Program: 0.146-0.163; fp8
# 0.68-0.72. (``mlp_norm`` stands with the other gains here, 0.060-0.073
# against 0.60: the gated shared expert carries its gradient too.)
ROUTED_GRAD_TOL = 0.32
ROUTED_LEAVES = ("router", "moe_gate", "moe_up", "moe_down")
# The two leaves of the DECAY, one number a value head each ([3, 32]):
# their gradient sums, over every token, a product that runs through the
# whole state, and what is left after the tokens cancel is small beside
# the bf16 roundings that entered at every chunk. Program: 0.045-0.101
# on ten seeds of eleven, 0.145 / 0.129 on one; fp8 0.75
# (``gdn_dt_bias``), 0.86 (``gdn_a_log``).
DECAY_GRAD_TOL = 0.32
DECAY_LEAVES = ("gdn_a_log", "gdn_dt_bias")
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest
# and 1, which a state left unchanged or a step of twice the length
# reads, nearer the former. Program: 0-5.6e-3 (the taps; a bf16 gain of
# 1.0 cannot move by 1e-5 and reads 0 on both sides); fp8 6.5e-3: not
# told apart, and not meant to be.
MOVED_TOL = 0.2
TOKEN_BLOCK = 2048
# Tokens between two states the reference's recurrence keeps for its
# backward pass (``jax.checkpoint`` a segment): memory, not mathematics.
SEGMENT = 64


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the delta rule TOKEN BY TOKEN as
# it is written (a ``lax.scan`` over tokens of multiply-and-sum: no
# chunk, no WY form, no matmul), the convolution as explicit shifted
# products, attention under an explicit mask, every held expert computed
# for every token and weighted, the K choices by K arg-maxes; no kernel,
# no sort, nothing imported from the program but the rule that says in
# which stack a layer's parameters lie (``LlamaConfig.layer_plan``).
# Follows Hugging Face's modeling_qwen3_next.py (the equations and the
# departures: horovod_tpu/models/reference.py). So that it fits at the
# cell's 2 x 8192 tokens the SAME math runs in blocks, as kind "afmoe"'s
# does (its ``_attend``, ``_over_blocks`` and ``_head_loss``): the
# linear_attention mixer a sequence at a time, its recurrence keeping a
# state every ``SEGMENT`` tokens for the backward pass, and the
# gradients a layer at a time. One block is the whole.
# ---------------------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """``S <- exp(g_t) S; r_t = v_t - S^T k_t; S <- S + k_t (beta_t
    r_t)^T; o_t = S^T q_t`` from ``S_0 = 0``, token by token, for ``q``,
    ``k`` [B, T, H, dk], ``v`` [B, T, H, dv], ``g``, ``beta`` [B, T, H],
    float32 -> ``o`` [B, T, H, dv]."""
    def token(S, x):
        q, k, v, g, beta = x                              # [B, H, ...]
        S = jnp.exp(g)[..., None, None] * S
        r = v - jnp.sum(S * k[..., None], -2)
        S = S + k[..., None] * (beta[..., None] * r)[..., None, :]
        return S, jnp.sum(S * q[..., None], -2)

    b, t, h, dk = q.shape
    seg = _block(t, SEGMENT)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(token, S, x)),
        jnp.zeros((b, h, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1)


def gated_delta_net(h, lp, c):
    """The linear_attention mixer on normalized ``h`` [B, T, D] with one
    layer's float32 parameters (``gdn_in`` columns ``[q | k | v | z]``,
    ``gdn_ba`` ``[b | a]``, each head by head)."""
    b, t, _ = h.shape
    hk, hv = c.linear_key_heads, c.linear_value_heads
    dk, dv = c.linear_key_dim, c.linear_value_dim
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
    o = delta_rule(
        jnp.repeat(q, hv // hk, 2), jnp.repeat(k, hv // hk, 2),
        u[..., 2 * kw:].reshape(b, t, hv, dv),
        -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(
            ba[..., hv:] + lp["gdn_dt_bias"]),
        jax.nn.sigmoid(ba[..., :hv]))
    y = _rms(o, lp["gdn_out_norm"], c.norm_eps) \
        * jax.nn.silu(z.reshape(b, t, hv, dv))
    return y.reshape(b, t, vw) @ lp["gdn_out"]


def _routed_and_shared(h, lp, c):
    """The expert layer's FFN on tokens ``h`` [n, D] -> (y [n, D], the
    tokens that chose each expert HELD here [held])."""
    n, k_top = c.n_experts, c.n_experts_per_token
    first, held = c.first_expert, c.n_experts_held or c.n_experts
    p = jax.nn.softmax(h @ lp["router"], -1)                 # [n, E]
    left, chosen = p, jnp.zeros_like(p)
    for _ in range(k_top):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -1.0, left)
    w = chosen * p
    w = w / jnp.sum(w, -1, keepdims=True)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, lp["moe_gate"])) \
        * jnp.einsum("td,edf->tef", h, lp["moe_up"])
    y = jnp.einsum("tef,efd->ted", act, lp["moe_down"])
    y = jnp.einsum("te,ted->td", w[:, first:first + held], y) \
        + jax.nn.sigmoid(h @ lp["shared_score"]) * _swiglu(
            h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return y, jnp.sum(chosen[:, first:first + held], 0)


def reference_layer(lp, x, c, linear):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: a ``linear`` (Gated DeltaNet) mixer or gated
    attention (RoPE on the first ``partial_rotary`` dimensions of a
    head, causal, 16 heads on 2), then the expert layer. -> (x, the
    tokens that chose each held expert [held])."""
    hd, rot = c.head_dim, c.partial_rotary or c.head_dim
    b, t, d = x.shape
    with jax.default_matmul_precision("highest"):
        if linear:
            h = _rms(x, lp["gdn_norm"], c.norm_eps)
            x = x + _over_blocks(
                lambda h, lp: gated_delta_net(h, lp, c), h, 1, lp
            ).reshape(b, t, d)
        else:
            h = _rms(x, lp["attn_norm"], c.norm_eps)
            q = _rms((h @ lp["wq"]).reshape(b, t, c.n_heads, hd),
                     lp["q_norm"], c.norm_eps)
            k = _rms((h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd),
                     lp["k_norm"], c.norm_eps)
            v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
            inv = c.rope_theta ** (-jnp.arange(0, rot // 2, dtype=F32)
                                   / (rot // 2))
            ang = jnp.arange(t, dtype=F32)[:, None] * inv   # [T, rot/2]
            cos, sin = (f(ang)[None, :, None] for f in (jnp.cos, jnp.sin))

            def rope(x):
                x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
                return jnp.concatenate(
                    [x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                     x[..., rot:]], -1)

            a = _attend(rope(q), rope(k), v, 0).reshape(b, t, -1)
            x = x + (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]

        h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
        y, load = _over_blocks(lambda h, lp: _routed_and_shared(h, lp, c),
                               h, _block(b * t, TOKEN_BLOCK), lp)
        x = x + y.reshape(b, t, d)
    return x, jnp.sum(load, 0)


def _linear(spec):
    """A layer of ``LlamaConfig.layer_plan`` -> is it linear_attention?"""
    return spec.mixer == "linear"


def reference_params(params, c):
    """The program's parameter tree (stacks by kind of layer, any
    storage dtype) -> float32, one dict a layer, in the model's order."""
    f32 = jax.tree.map(lambda w: w.astype(F32), params)
    out = {k: f32[k] for k in ("embed", "final_norm", "lm_head")}
    out["layers"] = [jax.tree.map(lambda w: w[spec.index], f32[spec.stack])
                     for spec in c.layer_plan()]
    return out


def reference_forward(p, tokens, c):
    """``p`` from :func:`reference_params`; tokens [B, T] -> the hidden
    state the head reads [B, T, D]."""
    x = p["embed"][tokens]
    for spec, lp in zip(c.layer_plan(), p["layers"]):
        x, _ = reference_layer(lp, x, c, _linear(spec))
    return x


def reference_logits(p, tokens, c):
    with jax.default_matmul_precision("highest"):
        return _rms(reference_forward(p, tokens, c), p["final_norm"],
                    c.norm_eps) @ p["lm_head"]


def reference_loss(p, batch, c):
    return _head_loss(p["final_norm"], p["lm_head"],
                      reference_forward(p, batch["tokens"], c),
                      batch["targets"], c.norm_eps)


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program a kind of layer (linear_attention or
    full_attention: two in the cell), whatever the depth: the layer, the
    tokens that chose each held expert, and its VJP under ``dy``. The
    forward sweep runs it too, with a zero ``dy`` and its gradients
    dropped (kind "afmoe" says why)."""
    def layer(linear):
        def run(lp, x, dy):
            y, vjp, load = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, linear),
                lp, x, has_aux=True)
            return y, load, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer={linear: layer(linear)
               for linear in {_linear(spec) for spec in c.layer_plan()}},
        embed=jax.jit(lambda e, t: e[t]),
        head=jax.jit(jax.value_and_grad(
            lambda g, w, x, t: _head_loss(g, w, x, t, c.norm_eps),
            argnums=(0, 1, 2))),
        d_embed=jax.jit(lambda dx, t: jnp.zeros(
            (c.vocab_size, c.d_model), F32).at[t].add(dx)))


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` and its gradient in every leaf
    of ``params`` (the program's tree), a layer at a time: forward
    keeping each layer's input, then the head, then the layers from the
    last to the first, each recomputed under ``jax.vjp``. ``visit(where,
    grads)`` is handed each set of float32 gradients as it is known
    (``where``: ``()`` for the top level's leaves, else (stack, index));
    nothing of them is kept here. -> (loss, [tokens that chose each held
    expert, a layer])."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    plan = c.layer_plan()

    def layer(spec):
        return _unstack(round_to)(params[spec.stack], spec.index)

    x = run.embed(read(params["embed"]), tokens)
    inputs, loads, no_dy = [], [], jnp.zeros_like(x)
    for spec in plan:
        inputs.append(x)
        x, load, _ = run.layer[_linear(spec)](layer(spec), x, no_dy)
        loads.append(load)
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), read(params["lm_head"]), x,
        batch["targets"])
    del x
    visit((), {"final_norm": d_norm, "lm_head": d_head})
    del d_norm, d_head
    for spec in reversed(plan):
        _, _, (d_lp, dx) = run.layer[_linear(spec)](layer(spec),
                                                    inputs.pop(), dx)
        visit((spec.stack, spec.index), d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(dx, tokens)})
    return loss, loads


def _rule_weighted(q, k, v, g, beta, w):
    out = delta_rule(q, k, v, g, beta)
    return jnp.sum(out * w), out


@jax.jit
def reference_rule(q, k, v, g, beta, w):
    """The recurrence token by token in float32 on ``q``, ``k``, ``v``
    (any dtype, read as float32), ``g``, ``beta`` and the gradients of
    ``sum(out * w)`` -> (out, dq, dk, dv, dg, dbeta), float32."""
    grads, out = jax.grad(_rule_weighted, argnums=(0, 1, 2, 3, 4),
                          has_aux=True)(
        *(x.astype(F32) for x in (q, k, v, g, beta, w)))
    return (out,) + grads


@jax.jit
def _program_rule(q, k, v, g, beta, w):
    from horovod_tpu.ops.gated_delta_rule import gated_delta_rule

    def f(q, k, v, g, beta, w):   # w rides as an argument
        out = gated_delta_rule(q, k, v, g, beta)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    grads, out = jax.grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        q, k, v, g, beta, w)
    return (out,) + grads


@functools.partial(jax.jit, static_argnames=("shape", "dk"))
def _rule_operands(key, shape, dk):
    """Operands as the mixer hands them to the rule: unit ``q`` (scaled)
    and ``k`` [B, T, H, dk] and ``v``, ``w`` [B, T, H, dv] in bf16;
    float32 ``g`` = -A softplus(a) with A log-uniform over (1e-3, 16) a
    head (weak and strong decays side by side) and ``beta`` = sigmoid of
    a normal a token and head."""
    ks = jax.random.split(key, 7)
    b, t, h, dv = shape

    def unit(k):
        x = jax.random.normal(k, (b, t, h, dk), F32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    a = jnp.exp(jax.random.uniform(ks[4], (h,), F32, np.log(1e-3),
                                   np.log(16.0)))
    return ((unit(ks[0]) * dk ** -0.5).astype(jnp.bfloat16),
            unit(ks[1]).astype(jnp.bfloat16),
            jax.random.normal(ks[2], shape, jnp.bfloat16),
            -a * jax.nn.softplus(jax.random.normal(ks[5], (b, t, h), F32)),
            jax.nn.sigmoid(2.0 * jax.random.normal(ks[6], (b, t, h), F32)),
            jax.random.normal(ks[3], shape, jnp.bfloat16))


# ---------------------------------------------------------------------
# What a step REQUIRES, from shapes and the rows held (beside
# ``afmoe_counts.py`` and ``gdn_counts.py``).
# ---------------------------------------------------------------------

def matmul_params_per_token(c, linear_layers, attn_layers,
                            routed_per_token):
    """Parameters that multiply ONE token on this chip: a
    linear_attention layer's three projections, a full_attention layer's
    four and its output gate; in every layer the router (scored against
    ALL experts), the shared expert with its gate, and
    ``routed_per_token`` held experts; the head over the vocabulary rows
    held. Not the lookup (a gather), not the norm gains, the taps or the
    per-head gates (elementwise)."""
    d = c.d_model
    kw = c.linear_key_heads * c.linear_key_dim
    vw = c.linear_value_heads * c.linear_value_dim
    expert = 3 * d * c.expert_width
    return (linear_layers * d * (2 * kw + 3 * vw + 2 * c.linear_value_heads)
            + attn_layers * d * c.head_dim * (3 * c.n_heads
                                              + 2 * c.n_kv_heads)
            + c.n_layers * (d * c.n_experts + c.n_shared_experts * expert
                            + d + routed_per_token * expert)
            + d * c.vocab_size)


# ---------------------------------------------------------------------

class Model(afmoe.Model):
    """Kind "afmoe"'s adapter (the share's counts, the kept batch, the
    timed programs once more) with Qwen3-Next's configuration, its
    counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert not config["tie_word_embeddings"] \
            and not config["mlp_only_layers"] \
            and config["decoder_sparse_step"] == 1, config
        assert config["shared_expert_intermediate_size"] \
            == config["moe_intermediate_size"], config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            n_experts=config["reduced"]["num_experts"]["published"],
            first_expert=a["first_expert"],
            layer_types=tuple(config["layer_types"]),
            partial_rotary=int(config["partial_rotary_factor"]
                               * config["head_dim"]),
            rope_full_attention=True, qk_norm="head", attn_gate=True,
            n_shared_experts=1, shared_expert_gate=True,
            score_func="softmax", moe_impl="grouped", moe_aux_weight=0.0,
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on
        self.rows_held = None      # a layer, from the reference's router

    # -- counts ---------------------------------------------------------

    def _mixers(self):
        return [spec.mixer for spec in self.cfg.layer_plan()]

    def gated_delta_rule_work(self):
        """(required FLOPs, required bytes) of the delta rule of a step:
        ``gdn_core_roofline_pct``'s numerator."""
        c, layers = self.cfg, self._mixers().count("linear")
        shape = (c.linear_value_heads, c.linear_key_dim,
                 c.linear_value_dim, layers)
        return (gdn_counts.rule_flops(self.units_per_step, *shape),
                gdn_counts.rule_bytes(
                    self.units_per_step, c.linear_key_heads, *shape,
                    jnp.dtype(c.compute_dtype).itemsize))

    def flops_per_unit(self):
        c, mixers, rows = self.cfg, self._mixers(), self._rows()
        params = matmul_params_per_token(
            c, mixers.count("linear"), mixers.count("attention"),
            sum(rows) / len(rows) / self.units_per_step)
        attn = mixers.count("attention") * afmoe_counts.attention_flops(
            1, self.seq, c.n_heads, c.head_dim) / self.seq
        rule = self.gated_delta_rule_work()[0] / self.units_per_step
        return 6 * params + attn + rule

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the delta rule in its chunked form
        (a triangular system a linear layer and phase, the chunk-major
        operands of the state-carrying scan) and no scan over tokens
        (which would read token-major operands), and on the chip the
        flash forward kernel and megablox's grouped GEMMs (jitted
        ``gmm`` and ``tgmm``), not their reference branches."""
        c = self.cfg
        lead = f"x{self.batch_size}x{c.linear_value_heads}x"
        for leading, meant in ((self.seq // 64, True), (self.seq, False)):
            if (f"tensor<{leading}{lead}" in text) != meant:
                return "grad program " + (
                    "lacks the chunk-major operands of the delta rule's "
                    "scan over chunks" if meant else
                    "holds token-major operands: a scan over tokens")
        if not on_tpu:
            return None
        linear = self._mixers().count("linear")
        solves = text.count("stablehlo.triangular_solve")
        if solves < 3 * linear:    # the CPU lowers them to LAPACK calls
            return f"grad program holds {solves} triangular systems for " \
                   f"{linear} linear_attention layers in three phases"
        missing = [name for name in ("tpu_custom_call", "hvd_flash_fwd",
                                     "@gmm", "@tgmm") if name not in text]
        if missing:
            return f"grad program lowered without {missing}: a " \
                   "kernel's reference branch ran"
        return None

    def check_outputs(self, params, key, say):
        """Returns a list of faults (empty = correct); see the module
        docstring for what is compared. As kind "afmoe": the timed
        programs come back from the compile cache, everything else
        compiled here stays out of it."""
        import time

        from jax.experimental.compilation_cache import compilation_cache

        began, heard = time.time(), say

        def say(**fields):   # how long the checks take is worth reading
            heard(seconds_into_checks=round(time.time() - began, 1),
                  **fields)

        c = self.cfg
        ks = jax.random.split(key, 4)
        tokens = jnp.asarray(self.trained_on) \
            if self.trained_on is not None \
            else lm.Model.batch(self, ks[3])["tokens"]
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        got = self._step_readings(params, batch, say)

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            faults = self._check_flash(ks[0], say)
            faults += self._check_rule(ks[1], say)
            bound = self.row_bound()
            for name, (k, n) in (("gate_up", (c.d_model, c.expert_width)),
                                 ("down", (c.expert_width, c.d_model))):
                faults += check_grouped_mm(
                    jax.random.fold_in(ks[2], k), bound, self.even_share,
                    k, n, c.n_experts_held, name, say, self._grouped_mm)
            return faults + self._check_step(params, batch, got, say)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _rule(self, q, k, v, g, beta, w):
        """-> (out, dq, dk, dv, dg, dbeta) of ``sum(out * w)``."""
        return _program_rule(q, k, v, g, beta, w)

    def _check_flash(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        kv = (self.batch_size, self.seq, c.n_kv_heads, c.head_dim)
        q, k, v, w = _normal(key, (shape, kv, kv, shape))
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._flash(q, k, v, w, 0),
            reference_attention(q, k, v, w, 0)))))
        say(event="flash_vs_explicit_mask", shape=list(shape),
            kv_heads=c.n_kv_heads,
            block_rows=_block(self.seq, afmoe.ATTENTION_BLOCK_ROWS),
            err=err, tol=KERNEL_TOL)
        return [f"flash {name} error {e} vs the explicit mask"
                for name, e in err.items()
                if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]]

    def _check_rule(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.linear_value_heads,
                 c.linear_value_dim)
        operands = _rule_operands(key, shape, c.linear_key_dim)
        err = dict(zip(("fwd", "dq", "dk", "dv", "dg", "dbeta"), map(
            float, _rel_errs(self._rule(*operands),
                             reference_rule(*operands)))))
        flops, nbytes = self.gated_delta_rule_work()
        dev = jax.local_devices()[0]
        say(event="delta_rule_vs_token_by_token", shape=list(shape),
            key_dim=c.linear_key_dim, err=err, tol=RULE_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=gdn_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"delta rule {name} error {e} vs the recurrence token by "
                "token" for name, e in err.items() if not e <= RULE_TOL]

    def _check_step(self, params, batch, got, say):
        """``got`` (:meth:`_step_readings`) against the reference on the
        same weights and batch; also says ``expert_load``."""
        c = self.cfg
        err = {}
        lr, eps = self.opt["learning_rate"], self.opt.get("eps", 1e-8)

        def visit(where, ref):
            trees = [{name: (tree[where[0]] if where else tree)[name]
                      for name in ref}
                     for tree in (got["grads"], params, got["after"])]
            readings = jax.device_get(_leaves_readings(
                *trees, ref, where[1] if where else None, lr, eps))
            for name, e in readings.items():
                for reading, value in e.items():
                    key = f"{reading}_{name}"
                    err[key] = max(err.get(key, 0.0), float(value))

        loss, loads = reference_loss_and_grads(params, batch, c, visit)
        loss = float(loss)
        err["loss"] = abs(float(got["loss"]) - loss) / abs(loss)
        self._say_expert_load(np.asarray(loads), batch, say)
        say(event="step_vs_reference", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            tol={"loss": LOSS_TOL, "d_": GRAD_TOL,
                 "d_ of " + ", ".join(ROUTED_LEAVES): ROUTED_GRAD_TOL,
                 "d_ of " + ", ".join(DECAY_LEAVES): DECAY_GRAD_TOL,
                 "moved_": MOVED_TOL},
            loss=float(got["loss"]), reference_loss=loss)
        return [f"the step's {name} error {e} vs the float32 reference"
                for name, e in err.items() if not e <= _bound(name)]


def _bound(reading):
    """The bound of a reading of ``step_vs_reference``."""
    if reading == "loss":
        return LOSS_TOL
    kind, leaf = reading.split("_", 1)
    if kind == "moved":
        return MOVED_TOL
    return ROUTED_GRAD_TOL if leaf in ROUTED_LEAVES \
        else DECAY_GRAD_TOL if leaf in DECAY_LEAVES else GRAD_TOL


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the four
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _flash(self, q, k, v, w, window):
        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, window)

    def _grouped_mm(self, lhs, rhs, cot, sizes):
        return afmoe._fp8_grouped_mm(lhs, rhs, cot, sizes)

    def _rule(self, q, k, v, g, beta, w):
        return reference_rule(_fp8(q), _fp8(k), _fp8(v), _fp8(g),
                              _fp8(beta), w)

    def _step_readings(self, params, batch, say):
        seen = {}
        loss, _ = reference_loss_and_grads(
            params, batch, self.cfg,
            lambda where, ref: seen.setdefault(where, {}).update(ref),
            round_to=FP8)
        grads = seen.pop(())
        for stack in {where[0] for where in seen}:
            n = len([w for w in seen if w[0] == stack])
            grads[stack] = {name: jnp.stack(
                [seen[stack, i][name] for i in range(n)])
                for name in params[stack]}
        say(event="the_reference_in_fp8_in_the_programs_place")
        return {"loss": loss, "grads": grads,
                "after": jax.tree.map(
                    lambda p, g: adam_first_step(p, g, self.opt), params,
                    grads)}


COMPARISONS = ("flash", "delta rule", "grouped GEMM", "the step")


def main(argv=None):
    """The control on the chip: the cell's run, two seconds of window,
    with ``Fp8InTheProgramsPlace``. Exits 0 when every comparison came
    out NOT correct, 1 when fp8 passed one."""
    import argparse
    import json
    import time

    t0 = time.time()
    from chipbench import child
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = child.find_cell("qwen3next.spmd.b2s8192")
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
    say(event="control", fp8_refused_by=refused,
        other_faults=[f for f in result["faults"]
                      if not any(f in fs for fs in refused.values())])
    lane.close()
    return 0 if all(refused.values()) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
