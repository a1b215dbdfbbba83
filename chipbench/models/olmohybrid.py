"""Model adapter for kind "olmohybrid": Olmo-Hybrid-7B's decoder (Gated
DeltaNet layers with keys 96 and values 192 wide and write strengths up
to 2, three to one beside position-free multi-head attention with a q/k
norm over the whole projected width, every part normed on its OUTPUT and
on nothing else, a dense SwiGLU in every layer, an untied head) as ONE
chip of the first of eight pipeline stages holds it: published layers
0-3 whole, an eighth of the vocabulary. Run through the program's own
``LlamaConfig`` / ``llama_init`` / ``llama_loss``, the path every LM
kind takes; this adapter extends kind "jamba"'s (the step of a state
that fills the chip, the flash comparison) and through it kind
"afmoe"'s (the batch it keeps, the comparisons' glue), and borrows kind
"qwen3next"'s recurrence token by token. Nothing of the model is
re-implemented here except the plain float32 reference that ``correct``
is decided against: the benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape (heads 128 wide, 30 on
   30) against an explicit-mask float32 attention computed in blocks of
   query rows, forward and gradients;
2. the program's gated delta rule (``ops/gated_delta_rule.py``, chunked)
   at [batch, seq, 30 heads, 96 / 192] with ``beta`` drawn over (0, 2)
   against the recurrence TOKEN BY TOKEN in float32, forward and the
   gradients of ``q``, ``k``, ``v``, ``g`` and ``beta``;
3. the chain round the rule (``ops/gdn_chain.py``: the convolution,
   SiLU and unit vectors before it, the gated norm behind it) at these
   widths against the float32 expression on the same operands, forward
   and the gradients of ``qkvz``, the taps, ``o``, ``z`` and the gain;
4. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a layer at a time and in blocks (the recurrence a
   sequence at a time, attention by query rows, the FFN and the head by
   token blocks): the loss; every gradient leaf (l2) but the decay's
   two, which are read and said and not judged (below: why); and the
   norm of every leaf's change under the reference's own first Adam
   step.

The control (``python3 -m chipbench.models.olmohybrid --seed N``): the
same run with the REFERENCE computed in fp8 put in the program's place
in all four comparisons, through the same verdicts; it has to come out
not correct in each.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts, gdn_chain_counts, gdn_counts
from chipbench.models import jamba, qwen3next
from chipbench.models.afmoe import (
    F32,
    FP8,
    _attend,
    _block,
    _fp8,
    _head_loss,
    _leaves_readings,
    _over_blocks,
    _rel_errs,
    _rms,
    _swiglu,
    _through,
    _unstack,
    adam_first_step,
)
from chipbench.models.qwen3next import delta_rule, reference_rule

# published config.json key -> LlamaConfig field
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
         "rms_norm_eps": "norm_eps",
         "linear_conv_kernel_dim": "conv_taps",
         "linear_num_key_heads": "linear_key_heads",
         "linear_num_value_heads": "linear_value_heads",
         "linear_key_head_dim": "linear_key_dim",
         "linear_value_head_dim": "linear_value_dim"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 61; PERF.md section 2): the largest the PROGRAM read
# over its seeds, and what the REFERENCE reads in the program's place
# with its matrices (for the kernels, its operands) rounded to fp8
# (e4m3, the nearest precision below the configuration's bf16), which
# has to fail (``Fp8InTheProgramsPlace``; seed 61424242).
# Flash at heads 128 wide, 30 on 30, bf16 operands, max-abs error over
# the largest entry; forward, backward: kind "jamba"'s comparison and
# its bound (``jamba.KERNEL_TOL``, 1.5e-2 both ways), which serves:
# program 0.0030-0.0044 / 0.0026-0.0059 over twenty-one runs on
# twenty-one seeds; fp8 0.052-0.065 / dq 0.044-0.056, dk 0.048-0.053,
# dv 0.022.
# The gated delta rule at 96 / 192 with beta over (0, 2), the same
# statistic: out, dq, dk, dv, dg, dbeta. Program 0.0062-0.0115 (what
# kind "qwen3next" reads at 128 / 128 and beta under 1: the doubled
# write strength costs the chunked form nothing on keys drawn at
# random); fp8 0.076 (dq) - 0.111 (dk, dg).
RULE_TOL = 2.5e-2
# The chain's two stages, the same statistic: q, k, v, the gated norm,
# and the gradients of qkvz, the taps, o, z and the gain. Program
# 0.0027-0.0085 (bf16's rounding of each result); fp8 0.0285-0.042 (the
# gain's gradient, a sum over every token and head), 0.039-0.041 (the
# taps'), 0.055-0.119 the other seven.
CHAIN_TOL = 1.5e-2
# The step. Loss, relative: program 2.7e-5 - 9.9e-5 (twenty-one runs); fp8
# 3.8e-2 - 3.9e-2.
LOSS_TOL = 3e-4
# A gradient leaf's l2 error, the worst layer. Program: ``final_norm``
# 0.006, the head, the attention layer's ``wo``, ``wv`` and q/k gains
# 0.019-0.025, ``wq``, ``wk`` 0.050-0.051, the gated norm's gain
# 0.084-0.106, the output norms' gains 0.114-0.161, the FFNs' matrices,
# ``gdn_out`` and the embedding 0.145-0.203 (twenty-one runs on
# twenty-one seeds); fp8 0.282-0.283 (``final_norm``), 0.336-0.385 (``wo``, the
# head, ``wv``), 0.47 (the q/k gains), 0.61-1.14 everywhere else. The
# limit stands two fifths above the program's largest, at fp8's
# smallest and a sixth under its next: fresh seeds read higher, and
# fp8 is refused by eighteen leaves whichever way ``final_norm`` falls.
# Twice
# what kind "qwen3next" reads below the same three rules: a write
# strength past 1 makes the state's transition along a key NEGATIVE, and
# a rounding that enters one layer's state alternates down the sequence
# instead of decaying with one sign; and no norm stands before a part to
# take a rounding's scale out again (on the CPU, bf16 against float32 at
# 256 wide: 0.10 at beta under 1 with input norms, 0.20 without them,
# 0.43 and 0.70 at beta up to 2; PERF.md section 6, PR 61).
GRAD_TOL = 0.28
# The leaves in FRONT of the rule (the projections to ``[q | k | v |
# z]`` and ``[b | a]``, the taps) have a bound of their own: their
# gradients come back through the rule's whole state, where those
# roundings alternate. Program: ``gdn_ba`` 0.155-0.215, ``gdn_in``
# 0.175-0.233, ``gdn_conv`` 0.211-0.275; fp8 1.15, 1.30, 1.55.
RULE_GRAD_TOL = 0.5
RULE_LEAVES = ("gdn_in", "gdn_ba", "gdn_conv")
# The two leaves of the DECAY, one number a head each ([3, 30]), are
# READ AND SAID, NOT JUDGED here: their gradient sums, over all 16,384
# tokens, a signed term a token (``dg`` times ``g``), and what is left
# after the tokens cancel can be as small as the roundings of the terms
# (a hundredth of the largest each: comparison 2 judges ``dg`` a token
# and head at the timed size). A share of that remainder has no scale
# of its own. Program: 0.054-0.129 on eighteen seeds of twenty-one,
# 0.236, 0.540 and 0.999 on three; fp8 0.61-0.90: no limit stands between
# (kind "qwen3next" gave its own a limit of 0.32 on 0.045-0.145 over
# eleven seeds: at ``beta`` to 2 the tail is heavier). Held where the
# arithmetic is float32: tests/single/test_olmohybrid_reference.py,
# 5e-3.
DECAY_LEAVES = ("gdn_a_log", "gdn_dt_bias")
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest
# and 1, which a state left unchanged reads, nearer the former.
# Program: 0-0.0057 (the taps; every matrix under 0.0003); fp8
# 0.0081-0.0104:
# not told apart, and not meant to be.
MOVED_TOL = 0.2
TOKEN_BLOCK = 2048
UNIT_EPS = 1e-6


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the delta rule TOKEN BY TOKEN as
# it is written (kind "qwen3next"'s ``delta_rule``: a ``lax.scan`` over
# tokens of multiply-and-sum, any ``dk``, ``dv`` and ``beta``), the
# convolution as explicit shifted products, attention under an explicit
# mask; no kernel, no chunk, nothing imported from the program but the
# rule that says in which stack a layer's parameters lie
# (``LlamaConfig.layer_plan``). The equations, what the published
# ``config.json`` gives and what is assumed:
# horovod_tpu/models/reference.py and configs/olmo-hybrid-7b.json. So
# that it fits at the cell's 2 x 8192 tokens the SAME math runs in
# blocks, as kind "qwen3next"'s does: the linear_attention mixer a
# sequence at a time, attention by query rows, the FFN and the head by
# token blocks, and the gradients a layer at a time. One block is the
# whole.
# ---------------------------------------------------------------------

def chain_in(qkvz, taps, hk, hv, dk, dv):
    """The chain's first stage in float32: ``qkvz`` [B, T, 2 hk dk + 2
    hv dv] = ``[q | k | v | z]`` and the taps [taps, 2 hk dk + hv dv] ->
    ``q``, ``k`` [B, T, hk, dk] (unit vectors a head, ``q`` times
    ``dk^-1/2``), ``v`` and ``z`` [B, T, hv, dv]."""
    b, t, _ = qkvz.shape
    kw, vw = hk * dk, hv * dv
    u, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
    n, conv = taps.shape[0], jnp.zeros_like(u)
    for j in range(n):
        back = n - 1 - j                     # u as it was ``back`` ago
        conv = conv + taps[j] * jnp.concatenate(
            [jnp.zeros((b, back, u.shape[-1]), F32), u[:, :t - back]], 1)
    u = jax.nn.silu(conv)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                 + UNIT_EPS)

    return (unit(u[..., :kw].reshape(b, t, hk, dk)) * dk ** -0.5,
            unit(u[..., kw:2 * kw].reshape(b, t, hk, dk)),
            u[..., 2 * kw:].reshape(b, t, hv, dv),
            z.reshape(b, t, hv, dv))


def chain_out(o, z, gain, eps):
    """The chain's second stage in float32: ``RMSNorm(o) * gain *
    SiLU(z)`` a head, ``o`` and ``z`` [B, T, hv, dv]."""
    return _rms(o, gain, eps) * jax.nn.silu(z)


def gated_delta_net(h, lp, c):
    """The linear_attention mixer on the stream ``h`` [B, T, D] (no norm
    before it) with one layer's float32 parameters (``gdn_in`` columns
    ``[q | k | v | z]``, ``gdn_ba`` ``[b | a]``, each head by head)."""
    b, t, _ = h.shape
    hk, hv = c.linear_key_heads, c.linear_value_heads
    ba = h @ lp["gdn_ba"]
    q, k, v, z = chain_in(h @ lp["gdn_in"], lp["gdn_conv"], hk, hv,
                          c.linear_key_dim, c.linear_value_dim)
    o = delta_rule(
        jnp.repeat(q, hv // hk, 2), jnp.repeat(k, hv // hk, 2), v,
        -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(
            ba[..., hv:] + lp["gdn_dt_bias"]),
        c.linear_beta_max * jax.nn.sigmoid(ba[..., :hv]))
    return chain_out(o, z, lp["gdn_out_norm"], c.norm_eps).reshape(
        b, t, -1) @ lp["gdn_out"]


def reference_layer(lp, x, c, linear):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: a ``linear`` (Gated DeltaNet) mixer or
    multi-head attention (q/k RMSNorm over the whole projected width,
    no position encoding, causal), then the SwiGLU; each part's OUTPUT
    under its RMSNorm, no part's input."""
    b, t, d = x.shape
    with jax.default_matmul_precision("highest"):
        if linear:
            mixed = _over_blocks(
                lambda h, lp: gated_delta_net(h, lp, c), x, 1, lp
            ).reshape(b, t, d)
        else:
            q = _rms(x @ lp["wq"], lp["q_norm"], c.norm_eps)
            k = _rms(x @ lp["wk"], lp["k_norm"], c.norm_eps)
            mixed = _attend(
                q.reshape(b, t, c.n_heads, c.head_dim),
                k.reshape(b, t, c.n_kv_heads, c.head_dim),
                (x @ lp["wv"]).reshape(b, t, c.n_kv_heads, c.head_dim),
                0).reshape(b, t, -1) @ lp["wo"]
        x = x + _rms(mixed, lp["post_attn_norm"], c.norm_eps)
        ff = _over_blocks(
            lambda h, lp: _swiglu(h, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]),
            x.reshape(b * t, d), _block(b * t, TOKEN_BLOCK), lp)
        return x + _rms(ff.reshape(b, t, d), lp["post_mlp_norm"],
                        c.norm_eps)


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
        x = reference_layer(lp, x, c, _linear(spec))
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
    full_attention), whatever the depth: the layer and its VJP under
    ``dy``. The forward sweep runs it too, with a zero ``dy`` and its
    gradients dropped (kind "afmoe" says why)."""
    def layer(linear):
        def run(lp, x, dy):
            y, vjp = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, linear), lp, x)
            return y, vjp(dy)
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
    nothing of them is kept here. -> the loss."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    plan = c.layer_plan()

    def layer(spec):
        return _unstack(round_to)(params[spec.stack], spec.index)

    x = run.embed(read(params["embed"]), tokens)
    inputs, no_dy = [], jnp.zeros_like(x)
    for spec in plan:
        inputs.append(x)
        x, _ = run.layer[_linear(spec)](layer(spec), x, no_dy)
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), read(params["lm_head"]), x,
        batch["targets"])
    del x
    visit((), {"final_norm": d_norm, "lm_head": d_head})
    del d_norm, d_head
    for spec in reversed(plan):
        _, (d_lp, dx) = run.layer[_linear(spec)](layer(spec), inputs.pop(),
                                                 dx)
        visit((spec.stack, spec.index), d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(dx, tokens)})
    return loss


# The chain's comparison: both stages and their gradients under seeded
# cotangent weights, in one program a side. ``sizes`` = (hk, hv, dk,
# dv, eps).

def _chain_weighted(stage_one, stage_two, qkvz, taps, o, z, gain, ws):
    outs = stage_one(qkvz, taps) + (stage_two(o, z, gain),)
    return sum(jnp.sum(out.astype(F32) * w.astype(F32))
               for out, w in zip(outs, ws)), outs


def _chain_readings(stage_one, stage_two, operands, ws):
    """-> (q, k, v, y; d qkvz, d taps, d o, d z, d gain)."""
    grads, outs = jax.grad(
        functools.partial(_chain_weighted, stage_one, stage_two),
        argnums=(0, 1, 2, 3, 4), has_aux=True)(*operands, ws)
    return outs[:3] + outs[4:] + grads


@functools.partial(jax.jit, static_argnames="sizes")
def reference_chain(operands, ws, sizes):
    """The float32 expressions on the operands read as float32."""
    hk, hv, dk, dv, eps = sizes
    return _chain_readings(
        lambda qkvz, taps: chain_in(qkvz, taps, hk, hv, dk, dv),
        lambda o, z, gain: chain_out(o, z, gain, eps),
        tuple(x.astype(F32) for x in operands), ws)


@functools.partial(jax.jit, static_argnames="sizes")
def _program_chain(operands, ws, sizes):
    """``ops/gdn_chain.py``'s two stages where the operands live, the
    expressions of ``models/llama.py`` elsewhere: as the mixer calls
    them."""
    from horovod_tpu.models import llama
    from horovod_tpu.ops import gdn_chain

    hk, hv, dk, dv, eps = sizes
    b, t, _ = operands[0].shape
    if gdn_chain.on_kernels(operands[0], dk, dv, hk, hv):
        def stage_one(qkvz, taps):
            q, k, v, z = gdn_chain.chain_in(qkvz, taps, hk, hv, dk, dv)
            return (q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk),
                    v.reshape(b, t, hv, dv), z.reshape(b, t, hv, dv))

        def stage_two(o, z, gain):
            return gdn_chain.chain_out(
                o.reshape(b, t, hv * dv), z.reshape(b, t, hv * dv), gain,
                eps).reshape(b, t, hv, dv)
    else:
        def stage_one(qkvz, taps):
            return llama._gdn_chain_in(qkvz, taps, hk, hv, dk, dv)

        def stage_two(o, z, gain):
            return llama._gdn_chain_out(o, z, gain, eps)
    return _chain_readings(stage_one, stage_two, operands, ws)


@functools.partial(jax.jit, static_argnames=("shape", "sizes"))
def _chain_operands(key, shape, sizes):
    """Operands as the mixer hands them to the chain, in bf16: ``qkvz``
    of unit variance, taps of the layer's start (fan-in scaled), ``o``
    three times unit variance (the norm has something to do), ``z``, a
    gain round 1; and the cotangent weights of q, k, v, z, y."""
    hk, hv, dk, dv, _ = sizes
    b, t = shape
    kw, vw = hk * dk, hv * dv
    ks = jax.random.split(key, 10)
    bf = jnp.bfloat16

    def normal(k, shape, scale=1.0):
        return (scale * jax.random.normal(k, shape, F32)).astype(bf)

    operands = (normal(ks[0], (b, t, 2 * kw + 2 * vw)),
                normal(ks[1], (4, 2 * kw + vw), 0.5),
                normal(ks[2], (b, t, hv, dv), 3.0),
                normal(ks[3], (b, t, hv, dv)),
                (1.0 + 0.3 * jax.random.normal(ks[4], (dv,), F32)
                 ).astype(bf))
    ws = tuple(normal(k, (b, t, h, d)) for k, (h, d) in zip(
        ks[5:], ((hk, dk), (hk, dk), (hv, dv), (hv, dv), (hv, dv))))
    return operands, ws


@functools.partial(jax.jit, static_argnames=("shape", "dk"))
def _rule_operands(key, shape, dk):
    """Kind "qwen3next"'s operands with the write strength drawn over
    (0, 2): ``beta`` = 2 sigmoid of a normal a token and head."""
    q, k, v, g, beta, w = qwen3next._rule_operands(key, shape, dk)
    return q, k, v, g, 2.0 * beta, w


# ---------------------------------------------------------------------
# What a step REQUIRES, from shapes (beside ``gdn_counts.py`` and
# ``gdn_chain_counts.py``).
# ---------------------------------------------------------------------

def matmul_params_per_token(c, linear_layers, attn_layers):
    """Parameters that multiply ONE token on this chip: a
    linear_attention layer's projections (``[q | k | v | z]``, ``[b |
    a]``, the output's), a full_attention layer's four, the SwiGLU of
    every layer, the head over the vocabulary rows held. Not the lookup
    (a gather), not the norm gains, the taps or the per-head gates
    (elementwise)."""
    d = c.d_model
    kw = c.linear_key_heads * c.linear_key_dim
    vw = c.linear_value_heads * c.linear_value_dim
    return (linear_layers * d * (2 * kw + 3 * vw + 2 * c.linear_value_heads)
            + attn_layers * d * c.head_dim * (2 * c.n_heads
                                              + 2 * c.n_kv_heads)
            + c.n_layers * 3 * d * c.d_ff + d * c.vocab_size)


# ---------------------------------------------------------------------

class Model(jamba.Model):
    """Kind "jamba"'s adapter (the kept batch, the step of a state that
    fills the chip, the flash comparison) with Olmo-Hybrid's
    configuration, its counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert not config["tie_word_embeddings"] \
            and not config["attention_bias"] \
            and config["hidden_act"] == "silu" \
            and config["linear_allow_neg_eigval"] \
            and config["rope_parameters"] == {"rope_theta": None}, config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            layer_types=tuple(config["layer_types"]),
            linear_beta_max=2.0, post_norm="only", qk_norm=True,
            rope_full_attention=False, loss_chunk=a["loss_chunk"],
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on

    # -- counts ---------------------------------------------------------

    def _gdn_shape(self):
        c = self.cfg
        return (self.units_per_step, c.linear_key_heads,
                c.linear_value_heads, c.linear_key_dim, c.linear_value_dim,
                self._mixers().count("linear"),
                jnp.dtype(c.compute_dtype).itemsize)

    def gated_delta_rule_work(self):
        """(required FLOPs, required bytes) of the delta rule of a step:
        ``gdn_core_roofline_pct``'s numerator."""
        tokens, hk, hv, dk, dv, layers, itemsize = self._gdn_shape()
        return (gdn_counts.rule_flops(tokens, hv, dk, dv, layers),
                gdn_counts.rule_bytes(tokens, hk, hv, dk, dv, layers,
                                      itemsize))

    def gdn_chain_work(self):
        """Required bytes of the chain round the rule of a step:
        ``gdn_chain_roofline_pct``'s numerator."""
        return gdn_chain_counts.chain_bytes(*self._gdn_shape())

    def flops_per_unit(self):
        c, mixers = self.cfg, self._mixers()
        params = matmul_params_per_token(c, mixers.count("linear"),
                                         mixers.count("attention"))
        attn = mixers.count("attention") * afmoe_counts.attention_flops(
            1, self.seq, c.n_heads, c.head_dim) / self.seq
        return 6 * params + attn \
            + self.gated_delta_rule_work()[0] / self.units_per_step

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the delta rule in its chunked form
        (the chunk-major kept states of the scan over chunks) and no
        scan over tokens (which would read token-major operands), and on
        the chip the flash forward kernel, the rule's pair and the
        chain's two pairs by name, not their reference branches, and no
        float32 array of the convolved columns (the chain's expression
        makes several)."""
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
        missing = [name for name in (
            "tpu_custom_call", "hvd_flash_fwd", "hvd_gdn_rule_fwd",
            "hvd_gdn_rule_bwd", "hvd_gdn_chain_in_fwd",
            "hvd_gdn_chain_in_bwd", "hvd_gdn_chain_out_fwd",
            "hvd_gdn_chain_out_bwd") if name not in text]
        if missing:
            return f"grad program lowered without {missing}: a " \
                   "kernel's reference branch ran"
        convolved = 2 * c.linear_key_heads * c.linear_key_dim \
            + c.linear_value_heads * c.linear_value_dim
        wide = f"tensor<{self.batch_size}x{self.seq}x{convolved}xf32>"
        if wide in text:
            return f"grad program holds {wide}: the chain's expression " \
                   "ran beside its kernels"
        return None

    def check_outputs(self, params, key, say):
        """Returns a list of faults (empty = correct); see the module
        docstring for what is compared. As kind "afmoe": the timed
        programs come back from the compile cache, everything else
        compiled here stays out of it."""
        import time

        from jax.experimental.compilation_cache import compilation_cache

        from chipbench.models import lm

        began, heard = time.time(), say

        def say(**fields):   # how long the checks take is worth reading
            heard(seconds_into_checks=round(time.time() - began, 1),
                  **fields)

        ks = jax.random.split(key, 4)
        tokens = jnp.asarray(self.trained_on) \
            if self.trained_on is not None \
            else lm.Model.batch(self, ks[3])["tokens"]
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        got, params = self._step_readings(params, batch, say)
        # The step's gradients and parameters (3.7 GB) wait on the HOST
        # while the kernels are compared: the chain's float32 expression
        # at the timed size holds 4.2 GB of its own.
        got = jax.device_get(got)

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            faults = (self._check_flash(ks[0], say)
                      + self._check_rule(ks[1], say)
                      + self._check_chain(ks[2], say))
            return faults + self._check_step(params, batch,
                                             jax.device_put(got), say)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _rule(self, q, k, v, g, beta, w):
        """-> (out, dq, dk, dv, dg, dbeta) of ``sum(out * w)``."""
        return qwen3next._program_rule(q, k, v, g, beta, w)

    def _chain(self, operands, ws, sizes):
        """-> (q, k, v, y; d qkvz, d taps, d o, d z, d gain)."""
        return _program_chain(operands, ws, sizes)

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
            key_dim=c.linear_key_dim,
            beta_max=float(jnp.max(operands[4])), err=err, tol=RULE_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=gdn_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"delta rule {name} error {e} vs the recurrence token by "
                "token" for name, e in err.items() if not e <= RULE_TOL]

    def _check_chain(self, key, say):
        c = self.cfg
        sizes = (c.linear_key_heads, c.linear_value_heads,
                 c.linear_key_dim, c.linear_value_dim, c.norm_eps)
        operands, ws = _chain_operands(
            key, (self.batch_size, self.seq), sizes)
        err = dict(zip(
            ("q", "k", "v", "y", "dqkvz", "dtaps", "do", "dz", "dgain"),
            map(float, _rel_errs(self._chain(operands, ws, sizes),
                                 reference_chain(operands, ws, sizes)))))
        nbytes = self.gdn_chain_work()
        dev = jax.local_devices()[0]
        say(event="chain_vs_float32_expression",
            shape=list(operands[0].shape), sizes=list(sizes[:4]), err=err,
            tol=CHAIN_TOL, required_bytes_per_step=nbytes,
            floor_ms=gdn_chain_counts.floor_s(dev.device_kind, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"chain {name} error {e} vs the float32 expression"
                for name, e in err.items() if not e <= CHAIN_TOL]

    def _check_step(self, params, batch, got, say):
        """``got`` (:meth:`_step_readings`) against the reference on the
        same weights and batch."""
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

        loss = float(reference_loss_and_grads(params, batch, c, visit))
        err["loss"] = abs(float(got["loss"]) - loss) / abs(loss)
        say(event="step_vs_reference", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            tol={"loss": LOSS_TOL, "d_": GRAD_TOL,
                 "d_ of " + ", ".join(RULE_LEAVES): RULE_GRAD_TOL,
                 "d_ of " + ", ".join(DECAY_LEAVES): "not judged",
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
    if leaf in DECAY_LEAVES:
        return float("inf")
    return RULE_GRAD_TOL if leaf in RULE_LEAVES else GRAD_TOL


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the four
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _flash(self, q, k, v, w, window):
        from chipbench.models.afmoe import reference_attention

        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, window)

    def _rule(self, q, k, v, g, beta, w):
        return reference_rule(_fp8(q), _fp8(k), _fp8(v), _fp8(g),
                              _fp8(beta), w)

    def _chain(self, operands, ws, sizes):
        return reference_chain(tuple(_fp8(x) for x in operands), ws, sizes)

    def _step_readings(self, params, batch, say):
        """Kind "jamba"'s: the reference's gradients wait on the host in
        the storage dtype (what the grad program hands back), a leaf a
        layer, and are stacked there."""
        from horovod_tpu.parallel import train_step

        train_step.drop_spare_gradients()
        seen = {}

        def keep(where, ref):
            seen.setdefault(where, {}).update(
                {name: np.asarray(g.astype(params["embed"].dtype))
                 for name, g in ref.items()})

        loss = reference_loss_and_grads(params, batch, self.cfg, keep,
                                        round_to=FP8)
        grads = seen.pop(())
        for stack in {where[0] for where in seen}:
            n = len([w for w in seen if w[0] == stack])
            grads[stack] = {name: np.stack(
                [seen[stack, i][name] for i in range(n)])
                for name in params[stack]}
        grads = jax.device_put(grads)
        say(event="the_reference_in_fp8_in_the_programs_place")
        return {"loss": loss, "grads": grads,
                "after": jax.tree.map(
                    lambda p, g: adam_first_step(p, g.astype(F32),
                                                 self.opt), params,
                    grads)}, params


COMPARISONS = ("flash", "delta rule", "chain", "the step")


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
    _, _, config, traffic = child.find_cell("olmohybrid.spmd.b2s8192")
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
