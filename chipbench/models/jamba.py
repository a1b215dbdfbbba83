"""Model adapter for kind "jamba": AI21-Jamba2-3B's decoder (Mamba-1
state-space layers thirteen to one beside attention layers of twenty
query heads on ONE key/value head, no position encoding, a dense SwiGLU
in every layer, the head tied to the embedding) as one pipeline stage's
chip holds it: every layer it runs whole, the whole vocabulary. Run
through the program's own ``LlamaConfig`` / ``llama_init`` /
``llama_loss``, the path every LM kind takes; this adapter extends kind
"afmoe"'s (the batch it keeps, the kernel comparisons' glue) and borrows
the blocked pieces of its reference. Nothing of the model is
re-implemented here except the plain float32 reference that ``correct``
is decided against: the benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape (heads 128 wide, 20 on
   1) against an explicit-mask float32 attention computed in blocks of
   query rows, forward and gradients;
2. the program's selective scan (``ops/selective_scan.py``) at [batch,
   seq, 5120 channels, 16 states] against the recurrence TOKEN BY TOKEN
   in float32, forward and the gradients of ``u``, ``dt``, ``A``, ``B``,
   ``C`` and ``D``;
3. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a layer at a time and in blocks (the recurrence a
   sequence at a time, attention by query rows, the FFN and the head by
   token blocks): the loss; EVERY gradient leaf (l2), the tied matrix's
   included; and the norm of every leaf's change under the reference's
   own first Adam step.

The control (``python3 -m chipbench.models.jamba --seed N``): the same
run with the REFERENCE computed in fp8 put in the program's place in all
three comparisons, through the same verdicts; it has to come out not
correct in each.
"""

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts, ssm_counts
from chipbench.models import afmoe, lm
from chipbench.models.afmoe import (
    F32,
    FP8,
    _attend,
    _block,
    _cache_counts,
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
    reference_attention,
)

# published config.json key -> LlamaConfig field
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
         "rms_norm_eps": "norm_eps", "mamba_d_conv": "conv_taps",
         "mamba_d_state": "mamba_d_state", "mamba_dt_rank": "mamba_dt_rank",
         "mamba_expand": "mamba_expand",
         "mamba_conv_bias": "mamba_conv_bias",
         "tie_word_embeddings": "tie_embeddings"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 47; PERF.md section 2): the largest the PROGRAM read
# over seven runs on seven seeds, and what the REFERENCE reads in the
# program's place with its matrices (for the kernels, its operands)
# rounded to fp8 (e4m3, the nearest precision below the configuration's
# bf16), which has to fail (``Fp8InTheProgramsPlace``; one seed).
# Flash at heads 128 wide, 20 on 1, bf16 operands, max-abs error over
# the largest entry; forward, backward. Program 0.0028 / 0.0031-0.0049;
# fp8 0.053 / dq 0.034, dk 0.042, dv 0.025.
KERNEL_TOL = {"fwd": 1.5e-2, "bwd": 1.5e-2}
# The selective scan, the same statistic: out, du, ddt, dA, dB, dC, dD.
# Program: out, du, dB, dC (rounded to bf16 as they leave) 0.0021-0.0030,
# ddt, dA, dD (float32) under 1e-6; fp8 0.030 (dD) - 0.134 (dA).
SCAN_TOL = 1e-2
# The step. Loss: fourteen layers of bf16 and a state that carries its
# roundings down the sequence; program 8.9e-4 - 1.2e-3; fp8 0.216.
LOSS_TOL = 1e-2
# A gradient leaf's l2 error, the worst layer. Program: ``ssm_in``
# 0.144-0.161, the tied matrix 0.142-0.159, ``ssm_a_log`` 0.126-0.144,
# ``ssm_norm``, ``ssm_d``, ``ssm_out``, the FFNs' matrices 0.110-0.134,
# the attention layer's under 0.11; fp8 0.415 (``final_norm``), 0.77
# (``wo``), 0.90-1.24 everywhere else.
GRAD_TOL = 0.27
# The leaves between the convolution and the scan have a bound of their
# own: their gradients pass through ``exp(dt A)`` and the state, which
# carry a rounding of ``dt``, ``B`` or ``C`` (bf16 as they leave the
# norms) down the sequence, and ``ssm_x``'s sums what is left of three
# such streams. Program: ``ssm_x`` 0.248-0.310, ``ssm_b_norm`` /
# ``ssm_c_norm`` 0.123-0.236, ``ssm_conv_bias`` 0.152-0.233,
# ``ssm_dt_bias``, ``ssm_dt``, ``ssm_dt_norm``, ``ssm_conv``
# 0.143-0.200; fp8 1.36 (``ssm_dt``) - 7.3 (``ssm_conv_bias``).
SCAN_GRAD_TOL = 0.65
SCAN_LEAVES = ("ssm_x", "ssm_b_norm", "ssm_c_norm", "ssm_conv",
               "ssm_conv_bias", "ssm_dt", "ssm_dt_bias", "ssm_dt_norm")
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest
# and 1, which a state left unchanged reads, nearer the former.
# Program: 0-0.0088 (the taps; most leaves under 0.001); fp8 0.012.
MOVED_TOL = 0.2
TOKEN_BLOCK = 2048
# Tokens between two states the reference's recurrence keeps for its
# backward pass (``jax.checkpoint`` a segment): memory, not mathematics.
SEGMENT = 64


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the selective scan TOKEN BY
# TOKEN as it is written (a ``lax.scan`` over tokens: no chunk, no
# kernel), the convolution as explicit shifted products, attention under
# an explicit mask; nothing imported from the program but the rule that
# says in which stack a layer's parameters lie
# (``LlamaConfig.layer_plan``). Follows Hugging Face's modeling_jamba.py
# (the equations and the departures: horovod_tpu/models/reference.py).
# So that it fits at the cell's 8192 tokens the SAME math runs in
# blocks, as kind "afmoe"'s does (its ``_attend``, ``_over_blocks`` and
# ``_head_loss``): the recurrence keeping a state every ``SEGMENT``
# tokens for the backward pass, and the gradients a layer at a time. One
# block is the whole.
# ---------------------------------------------------------------------

def selective_scan(u, dt, A, Bm, Cm, D):
    """``s_t = exp(dt_t A) s_{t-1} + dt_t u_t B_t; y_t = s_t . C_t + D
    u_t`` from ``s_0 = 0``, token by token, for ``u``, ``dt`` [B, T, C],
    ``A`` [C, N], ``Bm``, ``Cm`` [B, T, N], ``D`` [C], float32 -> ``y``
    [B, T, C]."""
    def token(s, x):
        u, dt, Bt, Ct = x                       # [B, C], [B, C], [B, N]
        s = jnp.exp(dt[..., None] * A) * s \
            + (dt * u)[..., None] * Bt[:, None, :]
        return s, jnp.sum(s * Ct[:, None, :], -1) + D * u

    b, t, c = u.shape
    seg = _block(t, SEGMENT)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1],
                                             *x.shape[2:])
               for x in (u, dt, Bm, Cm))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(token, s, x)),
        jnp.zeros((b, c, A.shape[1]), F32), xs)
    return jnp.moveaxis(y.reshape(t, b, c), 0, 1)


def mamba_mixer(h, lp, c):
    """The mamba mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters (``ssm_in`` columns ``[u | z]``, ``ssm_x`` ``[r |
    B | C]``)."""
    b, t, _ = h.shape
    n, r = c.mamba_d_state, c.mamba_dt_rank
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
        _rms(rbc[..., :r], lp["ssm_dt_norm"], c.norm_eps) @ lp["ssm_dt"]
        + lp["ssm_dt_bias"])
    y = selective_scan(
        u, dt, -jnp.exp(lp["ssm_a_log"]),
        _rms(rbc[..., r:r + n], lp["ssm_b_norm"], c.norm_eps),
        _rms(rbc[..., r + n:], lp["ssm_c_norm"], c.norm_eps), lp["ssm_d"])
    return (y * jax.nn.silu(z)) @ lp["ssm_out"]


def reference_layer(lp, x, c, mamba):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: a ``mamba`` mixer or attention (causal, no
    position encoding, 20 heads on 1), then the dense SwiGLU."""
    hd = c.head_dim
    b, t, d = x.shape
    with jax.default_matmul_precision("highest"):
        if mamba:
            h = _rms(x, lp["ssm_norm"], c.norm_eps)
            x = x + _over_blocks(
                lambda h, lp: mamba_mixer(h, lp, c), h, 1, lp
            ).reshape(b, t, d)
        else:
            h = _rms(x, lp["attn_norm"], c.norm_eps)
            q = (h @ lp["wq"]).reshape(b, t, c.n_heads, hd)
            k = (h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
            x = x + _attend(q, k, v, 0).reshape(b, t, -1) @ lp["wo"]
        h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
        y = _over_blocks(lambda h, lp: _swiglu(
            h, lp["w_gate"], lp["w_up"], lp["w_down"]), h,
            _block(b * t, TOKEN_BLOCK), lp)
    return x + y.reshape(b, t, d)


def _mamba(spec):
    """A layer of ``LlamaConfig.layer_plan`` -> is it a mamba layer?"""
    return spec.mixer == "mamba"


def _tied_head_loss(final_norm, embed, x, targets, eps):
    """Kind "afmoe"'s blocked head and loss with the embedding matrix
    [vocab, D] as the head."""
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
        x = reference_layer(lp, x, c, _mamba(spec))
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
    once a process: ONE program a kind of layer (mamba or attention),
    whatever the depth: the layer and its VJP under ``dy``. The forward
    sweep runs it too, with a zero ``dy`` and its gradients dropped
    (kind "afmoe" says why)."""
    def layer(mamba):
        def run(lp, x, dy):
            y, vjp = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, mamba), lp, x)
            return y, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer={mamba: layer(mamba)
               for mamba in {_mamba(spec) for spec in c.layer_plan()}},
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
    nothing of them is kept here. -> the loss."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    plan = c.layer_plan()

    def layer(spec):
        return _unstack(round_to)(params[spec.stack], spec.index)

    embed = read(params["embed"])
    x = run.embed(embed, tokens)
    inputs, no_dy = [], jnp.zeros_like(x)
    for spec in plan:
        inputs.append(x)
        x, _ = run.layer[_mamba(spec)](layer(spec), x, no_dy)
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), embed, x, batch["targets"])
    del x, embed
    visit((), {"final_norm": d_norm})
    del d_norm
    for spec in reversed(plan):
        _, (d_lp, dx) = run.layer[_mamba(spec)](layer(spec), inputs.pop(),
                                                dx)
        visit((spec.stack, spec.index), d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(d_head, dx, tokens)})
    return loss


def _scan_weighted(u, dt, A, Bm, Cm, D, w):
    out = selective_scan(u, dt, A, Bm, Cm, D)
    return jnp.sum(out * w), out


@jax.jit
def reference_scan(u, dt, A, Bm, Cm, D, w):
    """The recurrence token by token in float32 on the operands (any
    dtype, read as float32) and the gradients of ``sum(out * w)`` ->
    (out, du, ddt, dA, dB, dC, dD), float32."""
    grads, out = jax.grad(_scan_weighted, argnums=(0, 1, 2, 3, 4, 5),
                          has_aux=True)(
        *(x.astype(F32) for x in (u, dt, A, Bm, Cm, D, w)))
    return (out,) + grads


@jax.jit
def _program_scan(u, dt, A, Bm, Cm, D, w):
    from horovod_tpu.ops.selective_scan import selective_scan as scan

    def f(u, dt, A, Bm, Cm, D, w):   # w rides as an argument
        out = scan(u, dt, A, Bm, Cm, D)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    grads, out = jax.grad(f, argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(
        u, dt, A, Bm, Cm, D, w)
    return (out,) + grads


@functools.partial(jax.jit, static_argnames=("shape", "states"))
def _scan_operands(key, shape, states):
    """Operands as the mixer hands them to the scan: ``u`` after a SiLU
    and ``w`` [B, T, C] in bf16; ``dt`` = softplus of a normal round
    log-uniform starts over (1e-3, 0.1) a channel, float32 (small and
    large steps side by side); ``A[c, n] = -(n + 1)`` times a factor a
    channel near 1; unit-RMS ``B`` and ``C`` [B, T, N] in bf16; ``D``
    near 1."""
    ks = jax.random.split(key, 8)
    b, t, c = shape
    start = jnp.exp(jax.random.uniform(ks[1], (c,), F32, np.log(1e-3),
                                       np.log(0.1)))
    bias = start + jnp.log(-jnp.expm1(-start))     # softplus's inverse
    return (jax.nn.silu(jax.random.normal(ks[0], shape, F32)
                        ).astype(jnp.bfloat16),
            jax.nn.softplus(bias + jax.random.normal(ks[2], shape, F32)),
            -jnp.arange(1, states + 1, dtype=F32) * jnp.exp(
                0.2 * jax.random.normal(ks[3], (c, 1), F32)),
            jax.random.normal(ks[4], (b, t, states), jnp.bfloat16),
            jax.random.normal(ks[5], (b, t, states), jnp.bfloat16),
            1.0 + 0.1 * jax.random.normal(ks[6], (c,), F32),
            jax.random.normal(ks[7], shape, jnp.bfloat16))


# ---------------------------------------------------------------------
# What a step REQUIRES, from shapes (beside ``ssm_counts.py``).
# ---------------------------------------------------------------------

def matmul_params_per_token(c, mamba_layers, attn_layers):
    """Parameters that multiply ONE token: a mamba layer's four
    projections, an attention layer's four, the SwiGLU of every layer,
    the tied head over the vocabulary. Not the lookup (a gather), not
    the norm gains, the taps, the biases, ``A`` or ``D`` (elementwise)."""
    d, di = c.d_model, c.mamba_d_inner
    n, r = c.mamba_d_state, c.mamba_dt_rank
    return (mamba_layers * (d * 2 * di + di * (r + 2 * n) + r * di + di * d)
            + attn_layers * d * c.head_dim * (2 * c.n_heads
                                              + 2 * c.n_kv_heads)
            + c.n_layers * 3 * d * c.d_ff + d * c.vocab_size)


_TENSOR = re.compile(r"tensor<((?:\d+x)+)[a-z]")


def largest_tensor(text):
    """The elements of the largest tensor type a lowered program names."""
    return max((int(np.prod([int(n) for n in dims.split("x")[:-1]]))
                for dims in set(_TENSOR.findall(text))), default=0)


# ---------------------------------------------------------------------

class Model(afmoe.Model):
    """Kind "afmoe"'s adapter (the kept batch, the flash comparison's
    glue) with Jamba2's configuration, its counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert config["num_experts"] == 1 \
            and config["num_experts_per_tok"] == 1 \
            and not config["mamba_proj_bias"] \
            and config["sliding_window"] is None, config
        period, offset = (config["attn_layer_period"],
                          config["attn_layer_offset"])
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            layer_types=tuple(
                "full_attention" if i % period == offset else "mamba"
                for i in range(config["num_hidden_layers"])),
            d_head=a["head_dim"], loss_chunk=a["loss_chunk"],
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on

    # -- counts ---------------------------------------------------------

    def _mixers(self):
        return [spec.mixer for spec in self.cfg.layer_plan()]

    def selective_scan_work(self):
        """(required FLOPs, required bytes) of the selective scans of a
        step: ``ssm_core_roofline_pct``'s numerator."""
        c, layers = self.cfg, self._mixers().count("mamba")
        shape = (self.units_per_step, c.mamba_d_inner, c.mamba_d_state,
                 layers)
        return (ssm_counts.scan_flops(*shape), ssm_counts.scan_bytes(
            *shape, jnp.dtype(c.compute_dtype).itemsize))

    def flops_per_unit(self):
        c, mixers = self.cfg, self._mixers()
        params = matmul_params_per_token(c, mixers.count("mamba"),
                                         mixers.count("attention"))
        attn = mixers.count("attention") * afmoe_counts.attention_flops(
            1, self.seq, c.n_heads, c.head_dim) / self.seq
        return 6 * params + attn \
            + self.selective_scan_work()[0] / self.units_per_step

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must never name an array of tokens x
        channels x states (the scan materialised) and, on the chip, must
        hold the flash forward kernel and the scan's kernel pair by
        name, not their reference branches."""
        c = self.cfg
        whole = self.units_per_step * c.mamba_d_inner * c.mamba_d_state
        if largest_tensor(text) >= whole:
            return f"grad program names a tensor of {largest_tensor(text)} " \
                   f"elements: the scan's states materialised ({whole})"
        if not on_tpu:
            return None
        missing = [name for name in ("tpu_custom_call", "hvd_flash_fwd",
                                     "hvd_ssm_scan_fwd", "hvd_ssm_scan_bwd")
                   if name not in text]
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

        ks = jax.random.split(key, 3)
        tokens = jnp.asarray(self.trained_on) \
            if self.trained_on is not None \
            else lm.Model.batch(self, ks[2])["tokens"]
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        got, params = self._step_readings(params, batch, say)

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return (self._check_flash(ks[0], say)
                    + self._check_scan(ks[1], say)
                    + self._check_step(params, batch, got, say))
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _scan(self, *operands):
        """-> (out, du, ddt, dA, dB, dC, dD) of ``sum(out * w)``."""
        return _program_scan(*operands)

    def _step_readings(self, params, batch, say):
        """Kind "afmoe"'s, for a state that fills the chip: one more
        step of the TIMED programs on ``batch`` from ``params`` and a
        fresh optimizer state -> (its loss, the grad program's
        gradients, the parameters after it; ``params`` again). The apply
        program donates what it is given, and a second copy of 1.6 B
        parameters does not fit beside a step: the parameters wait on
        the HOST while the step runs, the step's result while the grad
        program runs."""
        from horovod_tpu.parallel import make_split_train_step, train_step

        on_tpu = jax.local_devices()[0].platform == "tpu"
        jk = {"compiler_options": self.compiler_options} \
            if on_tpu and self.compiler_options else {}

        def loss_fn(params, batch):
            return self.loss(params, (), batch)[0]

        before = _cache_counts()
        ts = make_split_train_step(loss_fn, self.optimizer(1),
                                   jit_kwargs=jk)
        carry = ts.init(params)
        # The grad program as the step builds it: where the device has
        # no room for two sets of gradients it writes into buffers it is
        # handed (``train_step.grad_program``).
        recycled = not train_step.holds_two_gradients(*carry)
        kept = jax.device_get(params)
        loss, (after, opt) = ts.step(carry, batch)
        del opt, params, carry, ts
        after = jax.device_get(after)
        params = jax.device_put(kept)
        del kept
        spare = (train_step.spare_gradients(params),) if recycled else ()
        _, grads = train_step.grad_program(loss_fn, recycled, jk)(
            params, batch, *spare)
        after = jax.device_put(after)
        jax.block_until_ready((grads, after))
        say(event="timed_programs_once_more",
            tokens=int(batch["tokens"].size), gradients_recycled=recycled,
            cache_before=before, cache=_cache_counts())
        return {"loss": loss, "grads": grads, "after": after}, params

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

    def _check_scan(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.mamba_d_inner)
        operands = _scan_operands(key, shape, c.mamba_d_state)
        err = dict(zip(("fwd", "du", "ddt", "dA", "dB", "dC", "dD"), map(
            float, _rel_errs(self._scan(*operands),
                             reference_scan(*operands)))))
        flops, nbytes = self.selective_scan_work()
        dev = jax.local_devices()[0]
        say(event="selective_scan_vs_token_by_token", shape=list(shape),
            states=c.mamba_d_state, err=err, tol=SCAN_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=ssm_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"selective scan {name} error {e} vs the recurrence token "
                "by token" for name, e in err.items() if not e <= SCAN_TOL]

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
                 "d_ of " + ", ".join(SCAN_LEAVES): SCAN_GRAD_TOL,
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
    return SCAN_GRAD_TOL if leaf in SCAN_LEAVES else GRAD_TOL


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the three
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _flash(self, q, k, v, w, window):
        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, window)

    def _scan(self, u, dt, A, Bm, Cm, D, w):
        return reference_scan(_fp8(u), _fp8(dt), _fp8(A), _fp8(Bm),
                              _fp8(Cm), _fp8(D), w)

    def _step_readings(self, params, batch, say):
        """The reference's gradients wait on the host in the storage
        dtype (what the grad program hands back), a leaf a layer, and
        are stacked there. No step of the program follows the window
        here: the gradient buffers it left go (3.2 GB the reference
        needs)."""
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


COMPARISONS = ("flash", "selective scan", "the step")


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
    _, _, config, traffic = child.find_cell("jamba2.spmd.b1s8192")
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
