"""Model adapter for kind "ouro": Ouro-2.6B's looped decoder (ONE stack of
layers of four norms run ``total_ut_steps`` = 4 times with shared
weights, the final norm closing every trip, an exit gate and the head
after every trip, the loss an expectation over the learned exit
distribution less its entropy) as ONE chip of the first of four pipeline
stages holds it: published layers 0-11 whole, all four trips, a quarter
of the vocabulary. Run through the program's own ``LlamaConfig`` /
``llama_init`` / ``llama_loss``, the path every LM kind takes; this
adapter extends kind "jamba"'s (the step of a state that fills the chip)
and through it kind "afmoe"'s (the batch it keeps, the comparisons' glue
and blocks). Nothing of the model is re-implemented here except the
plain float32 reference that ``correct`` is decided against: the
benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel pair at the cell's attention shape (heads 128 wide,
   16 on 16) on ROTATED queries and keys against an explicit-mask
   float32 attention computed in blocks of query rows, forward and three
   gradients;
2. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a layer VISIT at a time and in blocks (attention
   by query rows, the FFN and the exits' heads by token blocks): the
   loss; its three parts (the mean cross-entropy of each exit, the mean
   exit distribution, the mean entropy; the program's from
   ``llama_exit_terms``); every gradient leaf (l2), the shared stack's
   against the SUM of the reference's four visits and ALONG it, the
   gate's two leaves judged like every other; and the norm of every
   leaf's change under the reference's own first Adam step.

The control (``python3 -m chipbench.models.ouro --seed N``): the same
run with the REFERENCE computed in fp8 put in the program's place in
both comparisons, through the same verdicts; it has to come out not
correct in each.

What a faulty program would fail by: one that leaves the first or the
second trip's visits out of a shared leaf's gradient, or sums the four
wrongly by as much, by ``along_`` and ``d_`` of the stack's leaves (the
trips' shares of the stack's gradient by norm read 0.76-0.81, 0.41-0.44,
0.28-0.33 and 0.19-0.26 of the whole at these widths: the step says
them, ``visit_shares``); NOT, on the chip, one that leaves out the
third's or the last's (``ALONG_TOL`` says why; the CPU tests hold every
visit in float32); one that drops a TRIP in the forward pass by the loss
and the exits' cross-entropies (``ce_``: the exits differ by hundredths,
the bound is 2e-4); one that detaches the gate by ``d_exit_gate_w`` and
``d_exit_gate_b`` (1.0: no gradient arrives); one that norms outside the
loop (a trip starting from the unnormed stream) by the loss and every
part.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import ouro_counts
from chipbench.models import jamba
from chipbench.models.afmoe import (
    F32,
    FP8,
    _attend,
    _block,
    _fp8,
    _leaves_readings,
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
         "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
         "intermediate_size": "d_ff", "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps", "total_ut_steps": "loop_steps"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 64; PERF.md section 2): the largest the PROGRAM read over
# its seeds (seventeen runs on seventeen seeds; ``along_`` ten), and what the
# REFERENCE reads in the program's place with its matrices (for the
# kernels, its operands) rounded to fp8 (e4m3, the nearest precision
# below the configuration's bf16), which has to fail
# (``Fp8InTheProgramsPlace``; seeds 6400000002, 6400000034 and 6400000048).
# Flash at heads 128 wide, 16 on 16, rotated bf16 operands, max-abs error
# over the largest entry; forward, backward: kind "jamba"'s comparison
# and its bound (``jamba.KERNEL_TOL``, 1.5e-2 both ways), which serves:
# program 0.0020-0.0037 / 0.0025-0.0063; fp8 0.035-0.043 / dq
# 0.035-0.047, dk 0.039-0.057, dv 0.020-0.023.
KERNEL_TOL = jamba.KERNEL_TOL
# The step. Loss, relative: program 8.8e-6 - 3.8e-5; fp8 4.7e-3 - 8.8e-3.
LOSS_TOL = 3e-4
# The mean cross-entropy of each exit, relative: program 2.0e-7 - 6.5e-5;
# fp8 3.6e-4 - 6.8e-4 (the first exit: twelve visits of rounding),
# 1.0e-3 - 1.5e-3, 2.9e-3 - 4.6e-3, 6.4e-3 - 1.1e-2 (the fourth:
# forty-eight).
CE_TOL = 2e-4
# The mean exit distribution, an exit, relative: program 1.3e-5 - 7.2e-3;
# fp8 0.0058 and 0.0076 (an exit on two seeds of three: NOT told apart),
# 0.020-0.13 the other ten. A gate reads a hidden state whose every entry carries
# bf16's roundings through up to forty-eight visits; the mean over 8,192
# tokens keeps a few thousandths of it.
P_TOL = 1.5e-2
# The mean entropy, relative: program 1.7e-4 - 1.8e-3; fp8 0.013-0.063.
ENTROPY_TOL = 5e-3
# A gradient leaf's l2 error, the worst layer. Program: the head
# 0.020-0.027, the gate's bias 0.003-0.052 and weights 0.019-0.087, the
# closing norm's gain 0.087-0.18, the stack's eleven leaves and the
# embedding 0.14-0.26 by the seed (forty-eight visits of four norms each
# in bf16; kinds "jamba" and "olmohybrid" read 0.27 and 0.28 at their
# depths); fp8 0.42-0.51 (the head), 0.92-3.5 (the gate's weights),
# 1.6-2.4 (the closing norm), 2.3-4.6 everywhere else. The limit stands
# two fifths above the program's largest and an eighth under fp8's
# smallest; fp8 is refused by fifteen leaves whichever way the head
# falls.
GRAD_TOL = 0.37
# A shared leaf's gradient ALONG the reference's, ``|<g, r> / <r, r> -
# 1|``, the worst layer: rounding is spread over every direction and
# moves the projection little, a visit's share left out of the sum takes
# its whole projection away (the trips' gradients are nearly orthogonal:
# their shares' squares add up to 0.94-0.97). Program 0.011-0.055 by the
# seed (the largest of the eleven leaves, ten seeds); fp8 0.25-0.70.
# Left out of the sum, the first trip's visits would read 0.6, the
# second's 0.17-0.19, the third's 0.08-0.10 and the last's 0.04-0.07:
# the limit refuses the first two (so does ``d_``'s), NOT the third's
# and the last's, which lie inside the program's own spread at these
# widths and are held where the arithmetic is float32
# (tests/single/test_ouro_reference.py, every visit apart, 2e-5).
ALONG_TOL = 0.15
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest and
# 1, which a state left unchanged reads, nearer the former. Program:
# 0-8.1e-4; fp8 0-0.024: not told apart, and not meant to be.
MOVED_TOL = 0.2
TOKEN_BLOCK = 2048


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, Python loops over trips and layers, attention under an
# explicit mask, the rotation written out; no kernel, no scan, nothing
# imported from the program. The equations, what the published
# ``config.json`` gives and what is assumed:
# horovod_tpu/models/reference.py and configs/ouro-2.6b.json. So that it
# fits at the cell's 2 x 4096 tokens and 48 layer visits the SAME math
# runs in blocks, as kind "afmoe"'s does: attention by query rows, the
# FFN and the exits' heads by token blocks, and the gradients a layer
# VISIT at a time. One block is the whole.
# ---------------------------------------------------------------------

def rope(x, theta):
    """Half-split rotary embedding of ``x`` [B, T, H, hd] over the whole
    head at positions 0..T-1, float32."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def reference_layer(lp, x, c):
    """One VISIT of a layer on ``x`` [B,T,D] with its float32 parameters
    ``lp``: four norms, ``a = x + N(Attn(N(x)))``, ``a + N(SwiGLU(N(a)))``;
    multi-head attention, as many key/value heads as heads, rotated
    whole heads, causal."""
    b, t, d = x.shape
    shape = (b, t, c.n_heads, c.head_dim)
    with jax.default_matmul_precision("highest"):
        z = _rms(x, lp["attn_norm"], c.norm_eps)
        mixed = _attend(rope((z @ lp["wq"]).reshape(shape), c.rope_theta),
                        rope((z @ lp["wk"]).reshape(shape), c.rope_theta),
                        (z @ lp["wv"]).reshape(shape), 0
                        ).reshape(b, t, -1) @ lp["wo"]
        x = x + _rms(mixed, lp["post_attn_norm"], c.norm_eps)
        z = _rms(x, lp["mlp_norm"], c.norm_eps)
        ff = _over_blocks(
            lambda h, lp: _swiglu(h, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]),
            z.reshape(b * t, d), _block(b * t, TOKEN_BLOCK), lp)
        return x + _rms(ff.reshape(b, t, d), lp["post_mlp_norm"],
                        c.norm_eps)


def exit_distribution(gates):
    """Gate logits [R, ...] -> the exit distribution [R, ...]: ``p_t =
    sigmoid(s_t) prod_{j<t} sigmoid(-s_j)``, the last exit what is
    left."""
    lam = jax.nn.sigmoid(gates)
    left, p = jnp.ones_like(lam[0]), []
    for t in range(gates.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def exits_loss(lm_head, gate_w, gate_b, hs, targets, beta):
    """The loss from what the R exits read, ``hs`` [R, B, T, D] (each
    trip's output, normed), against ``targets`` [B, T], a block of
    tokens at a time: -> (loss, (the mean cross-entropy of each exit
    [R], the mean exit distribution [R], the mean entropy))."""
    R, n = hs.shape[0], targets.size
    rows = _block(n, TOKEN_BLOCK)

    def block(xt, lm_head, gate_w, gate_b):
        h, target = xt                                 # [rows, R, D]
        h = jnp.moveaxis(h, 1, 0)
        logp = jax.nn.log_softmax(h @ lm_head, -1)
        nll = -jnp.take_along_axis(
            logp, jnp.broadcast_to(target, (R, rows))[..., None], -1
        )[..., 0]
        p = exit_distribution(h @ gate_w + gate_b[0])
        entropy = -jnp.sum(p * jnp.log(p), 0)
        return (jnp.sum(jnp.sum(p * nll, 0) - beta * entropy),
                jnp.sum(nll, -1), jnp.sum(p, -1), jnp.sum(entropy))

    with jax.default_matmul_precision("highest"):
        sums = _over_blocks(
            block, (jnp.moveaxis(hs.reshape(R, n, -1), 0, 1),
                    targets.reshape(n)), rows, lm_head, gate_w, gate_b)
    loss, nll, p, entropy = (jnp.sum(s, 0) / n for s in sums)
    return loss, (nll, p, entropy)


def reference_params(params, c):
    """The program's parameter tree (any storage dtype) -> float32, the
    shared stack as one dict a layer."""
    f32 = jax.tree.map(lambda w: w.astype(F32), params)
    out = {k: v for k, v in f32.items() if k != "layers"}
    out["layers"] = [jax.tree.map(lambda w: w[i], f32["layers"])
                     for i in range(c.n_layers)]
    return out


def reference_exits(p, tokens, c):
    """``p`` from :func:`reference_params`; tokens [B, T] -> what the R
    exits read, [R, B, T, D]."""
    x, hs = p["embed"][tokens], []
    for _ in range(c.loop_steps):
        for lp in p["layers"]:
            x = reference_layer(lp, x, c)
        x = _rms(x, p["final_norm"], c.norm_eps)
        hs.append(x)
    return jnp.stack(hs)


def reference_loss(p, batch, c, terms=False):
    loss, parts = exits_loss(
        p["lm_head"], p["exit_gate_w"], p["exit_gate_b"],
        reference_exits(p, batch["tokens"], c), batch["targets"],
        c.exit_entropy_weight)
    return (loss, parts) if terms else loss


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program for a layer visit and its VJP under
    ``dy`` (the forward sweep runs it too, with a zero ``dy`` and its
    gradients dropped: kind "afmoe" says why), one for the norm that
    closes a trip, one for the exits."""
    def with_vjp(f):
        def run(w, x, dy):
            y, vjp = jax.vjp(f, w, x)
            return y, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer=with_vjp(lambda lp, x: reference_layer(lp, x, c)),
        close=with_vjp(lambda g, x: _rms(x, g, c.norm_eps)),
        embed=jax.jit(lambda e, t: e[t]),
        exits=jax.jit(jax.value_and_grad(
            lambda w, gw, gb, hs, t: exits_loss(
                w, gw, gb, hs, t, c.exit_entropy_weight),
            argnums=(0, 1, 2, 3), has_aux=True)),
        add=jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g)),
        norms=jax.jit(lambda g: jnp.sqrt(sum(
            jnp.sum(x * x) for x in jax.tree.leaves(g)))),
        d_embed=jax.jit(lambda dx, t: jnp.zeros(
            (c.vocab_size, c.d_model), F32).at[t].add(dx)))


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` and its gradient in every leaf
    of ``params`` (the program's tree), a layer VISIT at a time: forward
    through the R trips keeping each visit's input and each trip's
    stream before its closing norm, then the exits, then the trips from
    the last to the first, each visit recomputed under ``jax.vjp``; a
    shared leaf's gradient is the float32 SUM of its visits', added as
    they come. ``visit(where, grads)`` is handed each set of float32
    gradients when it is whole (``where``: ``()`` for the top level's
    leaves, else ("layers", index)). -> (the loss, its three parts, the
    norm of each trip's visits' gradients of the stack over the norm of
    their sum, the first trip's first)."""
    read, run = _through(round_to), _reference_programs(c)
    tokens, R, L = batch["tokens"], c.loop_steps, c.n_layers

    def layer(at):
        return _unstack(round_to)(params["layers"], at)

    gain = read(params["final_norm"])
    x = run.embed(read(params["embed"]), tokens)
    inputs, streams, hs, no_dy = [], [], [], jnp.zeros_like(x)
    for _ in range(R):
        for at in range(L):
            inputs.append(x)
            x, _ = run.layer(layer(at), x, no_dy)
        streams.append(x)
        x, _ = run.close(gain, x, no_dy)
        hs.append(x)
    del no_dy
    (loss, parts), (d_head, d_gate_w, d_gate_b, d_hs) = run.exits(
        read(params["lm_head"]), read(params["exit_gate_w"]),
        read(params["exit_gate_b"]), jnp.stack(hs), batch["targets"])
    del hs
    top = {"lm_head": d_head, "exit_gate_w": d_gate_w,
           "exit_gate_b": d_gate_b}
    del d_head
    summed, shares, dx = [None] * L, [], None
    for trip in reversed(range(R)):
        dh = d_hs[trip] if dx is None else d_hs[trip] + dx
        _, (d_gain, dx) = run.close(gain, streams.pop(), dh)
        top["final_norm"] = top.get("final_norm", 0.0) + d_gain
        of_trip = 0.0
        for at in reversed(range(L)):
            _, (d_lp, dx) = run.layer(layer(at), inputs.pop(), dx)
            of_trip = of_trip + run.norms(d_lp) ** 2
            summed[at] = d_lp if summed[at] is None \
                else run.add(summed[at], d_lp)
            del d_lp
        shares.append(float(of_trip) ** 0.5)
    top["embed"] = run.d_embed(dx, tokens)
    visit((), top)
    del top
    whole = float(sum(run.norms(g) ** 2 for g in summed)) ** 0.5
    for at in range(L):
        visit(("layers", at), summed[at])
        summed[at] = None
    return loss, parts, [s / whole for s in shares[::-1]]


@jax.jit
def _along(g, r, i):
    """Each leaf of the program's gradients ``g`` (layer ``i`` of a
    stack) along the reference's ``r``: ``|<g, r> / <r, r> - 1|``."""
    def one(g, r):
        g = jax.lax.dynamic_index_in_dim(g, i, keepdims=False).astype(F32)
        return jnp.abs(jnp.vdot(g, r) / jnp.vdot(r, r) - 1.0)
    return {name: one(g[name], r[name]) for name in r}


@functools.partial(jax.jit, static_argnames=("shape", "theta"))
def _rotated_operands(key, shape, theta):
    """Queries and keys as the layer hands them to the flash kernels:
    standard normal, ROTATED (float32, then bf16), values and cotangent
    weights standard normal."""
    q, k, v, w = (jax.random.normal(k, shape, F32)
                  for k in jax.random.split(key, 4))
    bf = jnp.bfloat16
    return (rope(q, theta).astype(bf), rope(k, theta).astype(bf),
            v.astype(bf), w.astype(bf))


# ---------------------------------------------------------------------

class Model(jamba.Model):
    """Kind "jamba"'s adapter (the kept batch, the step of a state that
    fills the chip) with Ouro's configuration, its counts and its
    comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert not config["tie_word_embeddings"] \
            and config["hidden_act"] == "silu" \
            and config["rope_scaling"] is None \
            and not config["use_sliding_window"] \
            and set(config["layer_types"]) == {"full_attention"}, config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            post_norm=True, exit_entropy_weight=a["exit_entropy_weight"],
            loss_chunk=a["loss_chunk"], dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on

    # -- counts ---------------------------------------------------------

    def exit_heads_work(self):
        """Required FLOPs of the R exits' heads of a step:
        ``exit_heads_roofline_pct``'s numerator."""
        c = self.cfg
        return ouro_counts.exit_heads_flops(
            self.units_per_step, c.loop_steps, c.d_model, c.vocab_size)

    def flops_per_unit(self):
        c = self.cfg
        return ouro_counts.train_flops_per_token(
            c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.n_layers, c.vocab_size, c.loop_steps, self.seq)

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the R exits side by side (the
        stacked hiddens the exits' head reads) and, on the chip, the
        flash forward kernel by name, not its reference branch."""
        c = self.cfg
        exits = f"tensor<{c.loop_steps}x{self.batch_size}x{self.seq}x" \
                f"{c.d_model}x"
        if exits not in text:
            return f"grad program lacks {exits}..>: the exits of " \
                   f"{c.loop_steps} trips"
        if not on_tpu:
            return None
        missing = [name for name in ("tpu_custom_call", "hvd_flash_fwd")
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

        from chipbench.models import lm

        began, heard = time.time(), say

        def say(**fields):   # how long the checks take is worth reading
            heard(seconds_into_checks=round(time.time() - began, 1),
                  **fields)

        ks = jax.random.split(key, 2)
        tokens = jnp.asarray(self.trained_on) \
            if self.trained_on is not None \
            else lm.Model.batch(self, ks[1])["tokens"]
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        got, params = self._step_readings(params, batch, say)

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            got["terms"] = self._terms(params, batch)
            return (self._check_flash(ks[0], say)
                    + self._check_step(params, batch, got, say))
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _terms(self, params, batch):
        """The program's own account of its loss's three parts."""
        from horovod_tpu.models import llama_exit_terms

        on_tpu = jax.local_devices()[0].platform == "tpu"
        jk = {"compiler_options": self.compiler_options} \
            if on_tpu and self.compiler_options else {}
        return jax.jit(lambda p, d: llama_exit_terms(p, d, self.cfg),
                       **jk)(params, batch)

    def _check_flash(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        q, k, v, w = _rotated_operands(key, shape, c.rope_theta)
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._flash(q, k, v, w, 0),
            reference_attention(q, k, v, w, 0)))))
        say(event="flash_vs_explicit_mask", shape=list(shape),
            kv_heads=c.n_kv_heads, rotated=True, err=err, tol=KERNEL_TOL)
        return [f"flash {name} error {e} vs the explicit mask"
                for name, e in err.items()
                if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]]

    def _check_step(self, params, batch, got, say):
        """``got`` (:meth:`_step_readings` and :meth:`_terms`) against
        the reference on the same weights and batch."""
        c = self.cfg
        err = {}
        lr, eps = self.opt["learning_rate"], self.opt.get("eps", 1e-8)

        def visit(where, ref):
            trees = [{name: (tree[where[0]] if where else tree)[name]
                      for name in ref}
                     for tree in (got["grads"], params, got["after"])]
            readings = jax.device_get(_leaves_readings(
                *trees, ref, where[1] if where else None, lr, eps))
            if where:    # the shared stack: a visit left out shows here
                for name, value in jax.device_get(
                        _along(trees[0], ref, where[1])).items():
                    readings[name]["along"] = value
            for name, e in readings.items():
                for reading, value in e.items():
                    key = f"{reading}_{name}"
                    err[key] = max(err.get(key, 0.0), float(value))

        loss, parts, shares = reference_loss_and_grads(params, batch, c,
                                                       visit)
        loss = float(loss)
        err["loss"] = abs(float(got["loss"]) - loss) / abs(loss)
        terms = {}
        for name, mine, ref in zip(("ce", "p", "entropy"), got["terms"],
                                   parts):
            mine, ref = (np.atleast_1d(np.asarray(x, np.float64))
                         for x in (mine, ref))
            terms[name] = ref.tolist()
            for i, (m, r) in enumerate(zip(mine, ref)):
                err[f"{name}_{i + 1}" if len(ref) > 1 else name] = \
                    abs(m - r) / abs(r)
        say(event="step_vs_reference", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            tol={"loss": LOSS_TOL, "ce_": CE_TOL, "p_": P_TOL,
                 "entropy": ENTROPY_TOL, "d_": GRAD_TOL,
                 "along_": ALONG_TOL, "moved_": MOVED_TOL},
            loss=float(got["loss"]), reference_loss=loss,
            reference_terms=terms,
            visit_shares=shares)
        return [f"the step's {name} error {e} vs the float32 reference"
                for name, e in err.items() if not e <= _bound(name)]


def _bound(reading):
    """The bound of a reading of ``step_vs_reference``."""
    return {"loss": LOSS_TOL, "ce": CE_TOL, "p": P_TOL,
            "entropy": ENTROPY_TOL, "along": ALONG_TOL,
            "moved": MOVED_TOL, "d": GRAD_TOL}[reading.split("_", 1)[0]]


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the two
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _flash(self, q, k, v, w, window):
        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, window)

    def _terms(self, params, batch):
        return self._fp8_terms

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

        loss, self._fp8_terms, _ = reference_loss_and_grads(
            params, batch, self.cfg, keep, round_to=FP8)
        grads = seen.pop(())
        grads["layers"] = {name: np.stack(
            [seen["layers", i][name] for i in range(self.cfg.n_layers)])
            for name in params["layers"]}
        grads = jax.device_put(grads)
        say(event="the_reference_in_fp8_in_the_programs_place")
        return {"loss": loss, "grads": grads,
                "after": jax.tree.map(
                    lambda p, g: adam_first_step(p, g.astype(F32),
                                                 self.opt), params,
                    grads)}, params


COMPARISONS = ("flash", "the step")


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
    _, _, config, traffic = child.find_cell("ouro.spmd.b2s4096")
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
