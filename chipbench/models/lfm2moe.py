"""Model adapter for kind "lfm2moe": LFM2-8B-A1B's decoder (gated
short-convolution layers beside attention layers, a leading dense layer,
expert layers with sigmoid routing and NO shared expert, the head tied
to the embedding) as ONE chip of the four that share each layer holds
it: experts ``first_expert .. + num_experts - 1`` of the published 32, a
slice of the vocabulary, every head. Run through the program's own
``LlamaConfig`` / ``llama_init`` / ``llama_loss`` with the grouped
dispatch, the path kinds "lm", "olmoe" and "afmoe" take; this adapter
extends kind "afmoe"'s (the share's counts, the batch it keeps, the
timed programs run once more, the kernel comparisons) and borrows the
blocked pieces of its reference. Nothing of the model is re-implemented
here except the plain float32 reference that ``correct`` is decided
against: the benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape (heads 64 wide, 32 on
   8) against an explicit-mask float32 attention computed in blocks of
   query rows, forward and gradients;
2. the grouped GEMM at the cell's shapes (expert width 1792, 8 groups,
   the share's row bound, uneven groups over about half of it), forward
   and both backward directions, against float32 ``numpy`` matmuls on
   whole groups (kind "afmoe"'s comparison);
3. the program's gated short convolution (``gated_short_conv``) at
   [batch, seq, 3 x hidden] against the float32 three-tap form, forward
   and the gradients of ``B``, ``C``, ``z`` and the taps;
4. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a layer at a time and in blocks: the loss; EVERY
   gradient leaf (l2), the tied matrix's included; and the norm of every
   leaf's change under the reference's own first Adam step.

Printed and not judged (``expert_load``): the rows the router hands the
experts held here, a layer, beside an even router's share.

The control (``python3 -m chipbench.models.lfm2moe --seed N``): the same
run with the REFERENCE computed in fp8 put in the program's place in all
four comparisons, through the same verdicts; it has to come out not
correct in each.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts, peaks
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
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
         "rope_theta": "rope_theta", "norm_eps": "norm_eps",
         "num_experts": "n_experts_held",
         "num_experts_per_tok": "n_experts_per_token",
         "num_dense_layers": "n_dense_layers",
         "norm_topk_prob": "norm_topk_prob",
         "routed_scaling_factor": "route_scale",
         "conv_L_cache": "conv_taps"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 34; PERF.md section 2): the largest the PROGRAM read
# over its seeds (eight: three whole runs and five in one process a
# window's steps into the batch), and what the REFERENCE reads in the
# program's place with its matrices (for the kernels, its operands)
# rounded to fp8 (e4m3, the nearest precision below the configuration's
# bf16), which has to fail (``Fp8InTheProgramsPlace``; one seed).
# Flash at heads 64 wide, bf16 operands, max-abs error over the largest
# entry; forward, backward. Program 0.0028-0.0030 / 0.0040-0.0053; fp8
# 0.059 / dq, dk 0.052-0.055, dv 0.025.
KERNEL_TOL = {"fwd": 1.5e-2, "bwd": 1.5e-2}
# The gated short convolution, the same statistic: out, dB, dC, dz, dw.
# Program 0.0033-0.0059; fp8 0.057 (dw) - 0.160 (out).
CONV_TOL = 1.5e-2
# The grouped GEMM at width 1792 reads what kind "afmoe"'s reads at 1024
# (program 0.0021-0.0034, fp8 0.038-0.045): its bound, 0.01, serves
# (``afmoe.GMM_TOL``, inside ``check_grouped_mm``).
# The step. Nine layers deep, and no shared expert beside the routed
# ones: a token whose choice of experts lies within bf16's rounding of
# an edge changes its whole FFN output, so the program stands further
# from the float32 reference here than in kind "afmoe"'s cell, and fp8
# further still. Loss: program 1.0e-5 - 3.0e-4; fp8 1.6e-2: told apart
# here.
LOSS_TOL = 1e-3
# A gradient leaf's l2 error, the worst layer. Program: ``final_norm``
# 0.019-0.027, the q/k gains 0.044-0.073, every matrix, the embedding
# and the other gains 0.106-0.149 (the attention projections the
# largest); fp8 0.55 (the q/k gains) - 0.74.
GRAD_TOL = 0.3
# The leaves the ROUTING reaches have a bound of their own, the gain of
# the norm in front of the experts among them (in an expert layer its
# gradient comes through the router and the routed experts alone).
# Program: ``mlp_norm`` 0.179-0.215, the held experts' matrices
# 0.296-0.312, the router 0.377-0.403; fp8 0.85, 0.92-0.94, 1.08.
ROUTED_GRAD_TOL = 0.6
ROUTED_LEAVES = ("router", "moe_gate", "moe_up", "moe_down", "mlp_norm")
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest
# and 1, which a state left unchanged or a step of twice the length
# reads, nearer the former. Program: 0 - 1.9e-3 (the router) for every
# leaf but the taps, which read 0 in five runs and 0.025-0.029 in three:
# at 1e-5 a bf16 tap of size 0.5 cannot move (its spacing is 4e-3), the
# few hundred near zero can, and which way each rounds follows its
# gradient's sign. fp8 3.3e-3 - 4.7e-3: not told apart, and not meant
# to be.
MOVED_TOL = 0.2
TOKEN_BLOCK = 2048


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the convolution as three
# explicit shifted products, attention under an explicit mask, every
# held expert computed for every token and weighted, the K choices by K
# arg-maxes; no kernel, no sort, nothing imported from the program but
# the rule that says in which stack a layer's parameters lie
# (``LlamaConfig.layer_plan``). Follows Hugging Face's
# modeling_lfm2_moe.py (the equations and the departures:
# horovod_tpu/models/reference.py). So that it fits at the cell's
# 2 x 8192 tokens the SAME math runs in blocks, as kind "afmoe"'s does
# (its ``_attend``, ``_over_blocks`` and ``_head_loss``), and the
# gradients a layer at a time. One block is the whole.
# ---------------------------------------------------------------------

def short_conv(proj, w):
    """``proj`` [B, T, 3D] = ``[B, C, z]`` side by side, ``w`` [taps, D]
    -> ``C * c``, ``c_t = sum_j w_j (B * z)_{t - (taps-1) + j}``, zero
    before position 0; float32."""
    b, t, d = proj.shape[0], proj.shape[1], proj.shape[2] // 3
    gate_in, gate_out, z = (proj[..., i * d:(i + 1) * d] for i in range(3))
    u = gate_in * z
    taps, conv = w.shape[0], jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                  # u as it was ``back`` ago
        conv = conv + w[j] * jnp.concatenate(
            [jnp.zeros((b, back, d), F32), u[:, :t - back]], 1)
    return gate_out * conv


def _routed(h, lp, c):
    """The expert layer's FFN on tokens ``h`` [n, D] -> (y [n, D], the
    tokens that chose each expert HELD here [held]). No shared expert."""
    n, k_top = c.n_experts, c.n_experts_per_token
    first, held = c.first_expert, c.n_experts_held or c.n_experts
    s = jax.nn.sigmoid(h @ lp["router"])                     # [n, E]
    left, chosen = s + lp["expert_bias"], jnp.zeros_like(s)
    for _ in range(k_top):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = chosen * s
    w = c.route_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, lp["moe_gate"])) \
        * jnp.einsum("td,edf->tef", h, lp["moe_up"])
    y = jnp.einsum("tef,efd->ted", act, lp["moe_down"])
    return (jnp.einsum("te,ted->td", w[:, first:first + held], y),
            jnp.sum(chosen[:, first:first + held], 0))


def reference_layer(lp, x, c, conv, dense):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: a ``conv`` mixer or attention (RoPE, causal, 32
    heads on 8), a ``dense`` FFN or the expert layer. -> (x, the tokens
    that chose each held expert [held]; zeros for a dense layer)."""
    hd = c.head_dim
    b, t, d = x.shape
    held = c.n_experts_held or c.n_experts
    with jax.default_matmul_precision("highest"):
        if conv:
            h = _rms(x, lp["conv_norm"], c.norm_eps)
            x = x + short_conv(h @ lp["conv_in"], lp["conv_w"]) \
                @ lp["conv_out"]
        else:
            h = _rms(x, lp["attn_norm"], c.norm_eps)
            q = _rms((h @ lp["wq"]).reshape(b, t, c.n_heads, hd),
                     lp["q_norm"], c.norm_eps)
            k = _rms((h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd),
                     lp["k_norm"], c.norm_eps)
            v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
            inv = c.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32)
                                   / (hd // 2))
            ang = jnp.arange(t, dtype=F32)[:, None] * inv    # [T, hd/2]
            cos, sin = (f(ang)[None, :, None] for f in (jnp.cos, jnp.sin))

            def rope(x):
                x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
                return jnp.concatenate(
                    [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

            x = x + _attend(rope(q), rope(k), v, 0).reshape(b, t, -1) \
                @ lp["wo"]

        h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
        rows = _block(b * t, TOKEN_BLOCK)
        if dense:
            y = _over_blocks(lambda h, lp: _swiglu(
                h, lp["w_gate"], lp["w_up"], lp["w_down"]), h, rows, lp)
            load = jnp.zeros((held,), F32)
        else:
            y, load = _over_blocks(
                lambda h, lp: _routed(h, lp, c), h, rows, lp)
            load = jnp.sum(load, 0)
        x = x + y.reshape(b, t, d)
    return x, load


def _kind(spec):
    """A layer of ``LlamaConfig.layer_plan`` -> (conv?, dense?)."""
    return spec.mixer == "conv", spec.dense_ffn


def _tied_head_loss(final_norm, embed, x, targets, eps):
    """Mean cross-entropy over the vocabulary rows held; the head is the
    embedding matrix [vocab, D]."""
    return _head_loss(final_norm, embed.T, x, targets, eps)


def reference_params(params, c):
    """The program's parameter tree (stacks by kind of layer, any
    storage dtype) -> float32, one dict a layer, in the model's order."""
    f32 = jax.tree.map(lambda w: w.astype(F32), params)
    out = {k: f32[k] for k in ("embed", "final_norm")}
    out["layers"] = [jax.tree.map(lambda w: w[spec.index], f32[spec.stack])
                     for spec in c.layer_plan()]
    return out


def reference_forward(p, tokens, c):
    """``p`` from :func:`reference_params`; tokens [B, T] -> the hidden
    state the head reads [B, T, D]."""
    x = p["embed"][tokens]
    for spec, lp in zip(c.layer_plan(), p["layers"]):
        x, _ = reference_layer(lp, x, c, *_kind(spec))
    return x


def reference_logits(p, tokens, c):
    with jax.default_matmul_precision("highest"):
        return _rms(reference_forward(p, tokens, c), p["final_norm"],
                    c.norm_eps) @ p["embed"].T


def reference_loss(p, batch, c):
    return _tied_head_loss(p["final_norm"], p["embed"],
                           reference_forward(p, batch["tokens"], c),
                           batch["targets"], c.norm_eps)


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program a kind of layer (conv or attention, a
    dense or an expert FFN: three in the cell), whatever the depth: the
    layer, the tokens that chose each held expert, and its VJP under
    ``dy``. The forward sweep runs it too, with a zero ``dy`` and its
    gradients dropped (kind "afmoe" says why)."""
    def layer(conv, dense):
        def run(lp, x, dy):
            y, vjp, load = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, conv, dense),
                lp, x, has_aux=True)
            return y, load, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer={kind: layer(*kind)
               for kind in {_kind(spec) for spec in c.layer_plan()}},
        embed=jax.jit(lambda e, t: e[t]),
        head=jax.jit(jax.value_and_grad(
            lambda g, e, x, t: _tied_head_loss(g, e, x, t, c.norm_eps),
            argnums=(0, 1, 2))),
        # the tied matrix's gradient: the head's plus the lookup's
        d_embed=jax.jit(lambda d_head, dx, t: d_head.at[t].add(dx)))


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` and its gradient in every leaf
    of ``params`` (the program's tree), a layer at a time: forward
    keeping each layer's input, then the head, then the layers from the
    last to the first, each recomputed under ``jax.vjp``. ``visit(where,
    grads)`` is handed each set of float32 gradients as it is known
    (``where``: ``()`` for the top level's leaves, else (stack, index));
    nothing of them is kept here. -> (loss, [tokens that chose each held
    expert, an expert layer])."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    plan = c.layer_plan()

    def layer(spec):
        return _unstack(round_to)(params[spec.stack], spec.index)

    embed = read(params["embed"])
    x = run.embed(embed, tokens)
    inputs, loads, no_dy = [], [], jnp.zeros_like(x)
    for spec in plan:
        inputs.append(x)
        x, load, _ = run.layer[_kind(spec)](layer(spec), x, no_dy)
        if not spec.dense_ffn:
            loads.append(load)
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), embed, x, batch["targets"])
    del x, embed
    visit((), {"final_norm": d_norm})
    del d_norm
    for spec in reversed(plan):
        _, _, (d_lp, dx) = run.layer[_kind(spec)](layer(spec),
                                                  inputs.pop(), dx)
        visit((spec.stack, spec.index), d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(d_head, dx, tokens)})
    return loss, loads


@jax.jit
def reference_short_conv(proj, w, cot):
    """The float32 three-tap form on ``proj`` [B, T, 3D], ``w`` [taps,
    D] (any dtype, read as float32) under the cotangent ``cot`` ->
    (out, d proj, d w)."""
    out, vjp = jax.vjp(short_conv, proj.astype(F32), w.astype(F32))
    return (out,) + vjp(cot.astype(F32))


# ---------------------------------------------------------------------
# What a step REQUIRES, from shapes and the rows held (beside
# ``afmoe_counts.py``, whose attention and grouped-GEMM counts serve).
# ---------------------------------------------------------------------

def matmul_params_per_token(c, conv_layers, attn_layers, routed_per_token):
    """Parameters that multiply ONE token on this chip: a conv layer's
    two projections (d x 3d, d x d), an attention layer's four, the
    dense layers' FFN; in an expert layer the router (scored against ALL
    experts) and ``routed_per_token`` held experts; the tied head over
    the vocabulary rows held. Not the lookup (a gather), not the norm
    gains, not the convolution's taps (elementwise)."""
    d = c.d_model
    expert_layers = c.n_layers - c.n_dense_layers
    return (conv_layers * 4 * d * d
            + attn_layers * d * c.head_dim * (2 * c.n_heads
                                              + 2 * c.n_kv_heads)
            + c.n_dense_layers * 3 * d * c.d_ff
            + expert_layers * (d * c.n_experts + routed_per_token * 3 * d
                               * c.expert_width)
            + d * c.vocab_size)


def short_conv_bytes(tokens, d_model, conv_layers, itemsize=2):
    """Bytes the convolution chains of a step must move if every operand
    is read and every result written once: forward reads ``[B, C, z]``
    (3d) and writes ``C * c`` (d); backward reads them and the cotangent
    (d) and writes ``d[B, C, z]`` (3d): 11 d elements a token and layer.
    The taps are 3 x d a layer: nothing. A forward that remat runs a
    second time is not credited."""
    return conv_layers * tokens * 11 * d_model * itemsize


# ---------------------------------------------------------------------

class Model(afmoe.Model):
    """Kind "afmoe"'s adapter (the share's counts, the kept batch, the
    timed programs once more) with LFM2-8B-A1B's configuration, its
    counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert config["use_expert_bias"] and not config["conv_bias"], config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            n_experts=config["reduced"]["num_experts"]["published"],
            first_expert=a["first_expert"],
            layer_types=tuple(config["layer_types"]),
            rope_full_attention=True, tie_embeddings=True,
            score_func="sigmoid", qk_norm="head",
            moe_impl="grouped", moe_aux_weight=0.0,
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

    def flops_per_unit(self):
        c, mixers, rows = self.cfg, self._mixers(), self._rows()
        params = matmul_params_per_token(
            c, mixers.count("conv"), mixers.count("attention"),
            sum(rows) / len(rows) / self.units_per_step)
        attn = mixers.count("attention") * afmoe_counts.attention_flops(
            1, self.seq, c.n_heads, c.head_dim) / self.seq
        return 6 * params + attn

    def short_conv_floor_s(self, device_kind):
        """The least time the chip could take for the step's convolution
        chains: their bytes over the published HBM bandwidth."""
        c = self.cfg
        return short_conv_bytes(
            self.units_per_step, c.d_model, self._mixers().count("conv"),
            jnp.dtype(c.compute_dtype).itemsize) \
            / peaks.peak(device_kind, "hbm_bytes_per_s")

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the flash forward kernel and
        megablox's grouped GEMMs (jitted ``gmm`` and ``tgmm``), not
        their reference branches."""
        if not on_tpu:
            return None
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
            faults += self._check_conv(ks[1], say)
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

    def _conv(self, proj, w, cot):
        """-> (out, d proj, d w) under the cotangent ``cot``."""
        return _program_short_conv(proj, w, cot)

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

    def _check_conv(self, key, say):
        c = self.cfg
        d = c.d_model
        shape = (self.batch_size, self.seq, 3 * d)
        proj, cot = _normal(key, (shape, shape[:2] + (d,)))
        w = _normal(jax.random.fold_in(key, 1), ((c.conv_taps, d),))[0]

        def streams(out, d_proj, d_w):
            """The gradient of each of the three streams apart."""
            return (out, *(d_proj[..., i * d:(i + 1) * d]
                           for i in range(3)), d_w)

        err = dict(zip(("fwd", "dB", "dC", "dz", "dw"), map(float, _rel_errs(
            streams(*self._conv(proj, w, cot)),
            streams(*reference_short_conv(proj, w, cot))))))
        dev = jax.local_devices()[0]
        say(event="short_conv_vs_three_taps", shape=list(shape),
            taps=c.conv_taps, err=err, tol=CONV_TOL,
            required_bytes_per_step=short_conv_bytes(
                self.units_per_step, d, self._mixers().count("conv")),
            floor_ms_at_the_hbm_peak=self.short_conv_floor_s(
                dev.device_kind) * 1e3 if dev.platform == "tpu" else None)
        return [f"short convolution {name} error {e} vs the three-tap "
                "form" for name, e in err.items() if not e <= CONV_TOL]

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
    return ROUTED_GRAD_TOL if leaf in ROUTED_LEAVES else GRAD_TOL


@jax.jit
def _program_short_conv(proj, w, cot):
    from horovod_tpu.models.llama import gated_short_conv

    out, vjp = jax.vjp(gated_short_conv, proj, w)
    return (out,) + vjp(cot)


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

    def _conv(self, proj, w, cot):
        return reference_short_conv(_fp8(proj), _fp8(w), cot)

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


COMPARISONS = ("flash", "short convolution", "grouped GEMM", "the step")


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
    _, _, config, traffic = child.find_cell("lfm2moe.spmd.b2s8192")
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
