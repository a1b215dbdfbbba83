"""Model adapter for kind "olmoe": OLMoE's sparse-expert decoder (64
experts, 8 a token, q/k RMSNorm over the whole projected width, top-k
probabilities not renormalised) run through the program's own
``LlamaConfig`` / ``llama_init`` / ``llama_loss`` with the dropless
grouped dispatch, exactly the path kind "lm" takes (whose adapter this
one extends). Nothing of the model
is re-implemented here except the plain float32 reference that
``correct`` is decided against: the benchmark's own copy (the program
keeps one in ``horovod_tpu/models/reference.py``, which a later PR may
edit; this one it may not).

What ``correct`` means for this kind, outside the window, at published
widths, on the weights the run ended with (bounds and the readings they
were set from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape against
   ``blockwise_attention``, as kind "lm" checks it;
2. the grouped GEMM at the cell's shapes, forward and both backward
   directions, against float32 ``numpy`` matmuls on whole groups;
3. on a seeded 512-token sample against the reference and ``jax.grad``
   of it: the logits; the loss over the tokens whose routing the
   reference decides by a clear margin, and that loss's gradients in
   one layer's experts (all 64, three matrices each), router and
   q/k-norm gains.

Printed and not judged: the tokens each expert is handed on a seeded
batch of the cell's shape, max and min over mean, per layer, from the
program's own router. That no routed slot is dropped is what (2) and
(3) hold the program to: the reference computes every expert for every
token, so a slot left out is a whole expert's output missing at a
decided token.
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import moe_counts
from chipbench.compare import rel_err
from chipbench.models import lm

F32 = jnp.float32


def l2_err(got, ref):
    """||got - ref|| / ||ref|| over all entries, in f32."""
    got, ref = got.astype(F32), ref.astype(F32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


# published config.json key -> LlamaConfig field
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps", "num_experts": "n_experts",
         "num_experts_per_tok": "n_experts_per_token",
         "norm_topk_prob": "norm_topk_prob"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 27; PERF.md section 2): the largest the program read in
# ten runs on ten seeds, and what the REFERENCE reads against itself
# when its weights are rounded to fp8 (e4m3, the nearest precision below
# the configuration's bf16), which has to fail.
# Flash, bf16 operands, rel_err: kind "lm"'s bounds, the smoke's.
KERNEL_TOL = lm.KERNEL_TOL
# Grouped GEMM, bf16 operands, f32 accumulation, result rounded to bf16
# once (2**-9 = 2e-3 of the largest entry is that rounding alone),
# rel_err: 0.0022-0.0033 read in every direction.
GMM_TOL = 1e-2
# bf16 weights and activations against the float32 reference on the same
# weights. A token whose 8th and 9th router probabilities lie closer than
# bf16 resolves takes another expert than the reference gives it: a
# whole expert's output at a few tokens, which a maximum over all tokens
# reads (0.08-0.11) and a norm over all tokens weighs by how few they
# are. So: the l2 error over ALL tokens (program 0.017-0.024; fp8
# 0.124-0.130) and the max-abs error over the tokens whose routing the
# reference decides by more than ROUTING_MARGIN at every layer, 108-181
# of 512 (program 0.011-0.018; fp8 0.134-0.158).
LOGITS_TOL = 5e-2
DECIDED_TOL = 5e-2
ROUTING_MARGIN = 0.05
# The loss over the decided tokens + the aux term. Program at most 2e-4;
# a missing aux term (0.01 x 8 of a loss of 10.4) reads 8e-3 and fails.
# It does NOT tell fp8 from bf16 (3e-4 to 1.7e-3): at random weights
# the loss is ln(vocab) whatever the precision.
LOSS_TOL = 1e-3
# l2 error of a gradient leaf of that loss. Program: experts 0.015-0.021,
# router 0.008-0.029, q/k-norm gains 0.036-0.051; fp8: 0.21-0.34,
# 0.13-0.38, 0.32-0.33.
GRAD_TOL = 1e-1
REFERENCE_TOKENS = 512
# The layer whose gradients are read, and the leaves: all 64 experts'
# three matrices (an expert the sample routes nothing to has a zero
# gradient on both sides), the router, both q/k-norm gains.
CHECKED_LAYER = 1
CHECKED_LEAVES = ("moe_gate", "moe_up", "moe_down", "router", "q_norm",
                  "k_norm")


# ---------------------------------------------------------------------
# The plain reference. Straightforward float32 jax.numpy under "highest"
# matmul precision: explicit mask, a Python loop over layers, every
# expert computed for every token and weighted by its routing
# probability (zero for those not chosen), the K choices by K arg-maxes;
# no kernel, no sort, no scan, no remat, nothing imported from the
# program. Follows Hugging Face's modeling_olmoe.py and arXiv:2409.02060.
# Departures: the paper's router z-loss (0.001) is in neither
# config.json nor Hugging Face's loss and is left out; bf16-stored
# parameters are read as float32 (exact).
# ---------------------------------------------------------------------

def reference_params(params):
    """The program's parameter tree (layers stacked on a leading axis,
    any storage dtype) -> float32, one dict a layer."""
    n = jax.tree.leaves(params["layers"])[0].shape[0]
    out = {k: v.astype(F32) for k, v in params.items() if k != "layers"}
    out["layers"] = [jax.tree.map(lambda w: w[i].astype(F32),
                                  params["layers"]) for i in range(n)]
    return out


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def reference_forward(p, tokens, c):
    """``p`` from :func:`reference_params`; tokens [B, T] -> (logits
    [B, T, vocab], the load-balancing term over all layers' tokens,
    margin [B, T]: how far, as a share of it, a token's K-th probability
    lies above its (K+1)-th, the least over the layers)."""
    hd = c.d_model // c.n_heads
    rep = c.n_heads // c.n_kv_heads
    b, t = tokens.shape
    n, k_top = c.n_experts, c.n_experts_per_token
    inv = c.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv           # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    mask = jnp.tril(jnp.ones((t, t), bool))
    all_probs, all_choices, margin = [], [], jnp.ones((b, t), F32)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        for lp in p["layers"]:
            h = _rms(x, lp["attn_norm"], c.norm_eps)
            q = _rms(h @ lp["wq"], lp["q_norm"], c.norm_eps)
            k = _rms(h @ lp["wk"], lp["k_norm"], c.norm_eps)
            q = rope(q.reshape(b, t, c.n_heads, hd))
            k = rope(k.reshape(b, t, c.n_kv_heads, hd))
            v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, -1)
            x = x + a @ lp["wo"]

            h = _rms(x, lp["mlp_norm"], c.norm_eps)
            probs = jax.nn.softmax(h @ lp["router"], -1)     # [B,T,E]
            left, weights, choices = probs, jnp.zeros_like(probs), []
            for _ in range(k_top):
                pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
                choices.append(pick)
                weights = weights + pick * probs
                left = jnp.where(pick > 0, -1.0, left)
            margin = jnp.minimum(margin, 1.0 - jnp.max(left, -1)
                                 / jnp.sum(pick * probs, -1))
            if c.norm_topk_prob:
                weights = weights / jnp.sum(weights, -1, keepdims=True)
            act = jax.nn.silu(jnp.einsum("btd,edf->btef", h,
                                         lp["moe_gate"])) \
                * jnp.einsum("btd,edf->btef", h, lp["moe_up"])
            y = jnp.einsum("btef,efd->bted", act, lp["moe_down"])
            x = x + jnp.einsum("bte,bted->btd", weights, y)
            all_probs.append(probs.reshape(-1, n))
            all_choices.append(jnp.stack(choices, -2).reshape(-1, k_top,
                                                              n))
        logits = _rms(x, p["final_norm"], c.norm_eps) @ p["lm_head"]
    f = jnp.mean(jnp.concatenate(all_choices, 0), 0)        # [K, E]
    prob = jnp.mean(jnp.concatenate(all_probs, 0), 0)       # [E]
    return logits, n * jnp.sum(f * prob[None, :]), margin


def reference_loss(p, batch, c, aux_weight):
    """Cross-entropy, the mean over the tokens ``batch["mask"]`` keeps
    (all without one), + ``aux_weight`` x the aux term over all."""
    logits, aux, _ = reference_forward(p, batch["tokens"], c)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    return jnp.sum(nll * mask) / jnp.sum(mask) + aux_weight * aux


# ---------------------------------------------------------------------

def _batch(key, batch, seq, vocab):
    tokens = jax.random.randint(key, (batch, seq), 0, vocab)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


class Model(lm.Model):
    """Kind "lm"'s adapter (init, loss, batch, optimizer through the
    program's llama functions) with OLMoE's configuration, counts and
    comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            qk_norm=True, moe_impl="grouped",
            moe_aux_weight=a["router_aux_loss_coef"],
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False

    def flops_per_unit(self):
        c = self.cfg
        return moe_counts.moe_train_flops_per_token(
            c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.n_layers, c.vocab_size, c.n_experts, c.n_experts_per_token,
            self.seq)

    def grouped_gemm_work(self):
        """(required FLOPs, required bytes) of the grouped GEMMs a step:
        what ``moe_gmm_roofline_pct`` divides by the kernels' time."""
        c = self.cfg
        shape = (self.units_per_step, c.n_experts_per_token, c.d_model,
                 c.d_ff, c.n_layers)
        return (moe_counts.grouped_gemm_flops_per_step(*shape),
                moe_counts.grouped_gemm_bytes_per_step(
                    *shape, c.n_experts,
                    jnp.dtype(c.compute_dtype).itemsize))

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the flash kernels AND megablox's
        grouped GEMMs (jitted ``gmm`` and ``tgmm``, which the lowering
        keeps as functions of those names), not their reference
        branches."""
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
        docstring for what is compared."""
        ks = jax.random.split(key, 4)
        faults = self._check_flash(ks[0], say)
        c = self.cfg
        rows = self.units_per_step * c.n_experts_per_token
        for name, (k, n) in (("gate_up", (c.d_model, c.d_ff)),
                             ("down", (c.d_ff, c.d_model))):
            faults += check_grouped_mm(jax.random.fold_in(ks[1], k), rows,
                                       k, n, c.n_experts, name, say)
        self._say_expert_load(params, ks[2], say)
        faults += self._check_against_reference(params, ks[3], say)
        return faults

    def _check_flash(self, key, say):
        from horovod_tpu.ops import flash_attention
        from horovod_tpu.parallel.ring_attention import blockwise_attention

        c = self.cfg
        ks = jax.random.split(key, 4)
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        kv = (self.batch_size, self.seq, c.n_kv_heads, c.head_dim)
        q, k, v, w = (jax.random.normal(kk, s, jnp.bfloat16) for kk, s
                      in zip(ks, (shape, kv, kv, shape)))

        def grads_of(attn):   # w rides as an argument, never closed over
            def f(q, k, v, w):
                out = attn(q, k, v, causal=True)
                return jnp.sum(out.astype(F32) * w.astype(F32)), out
            return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

        got, out = grads_of(flash_attention)(q, k, v, w)
        ref, out_ref = grads_of(blockwise_attention)(q, k, v, w)
        err = {"fwd": rel_err(out, out_ref)}
        for name, g, r in zip(("dq", "dk", "dv"), got, ref):
            err[name] = rel_err(g, r)
        say(event="flash_vs_blockwise", shape=list(shape), err=err,
            tol=KERNEL_TOL)
        return [f"flash {name} error {e} vs blockwise"
                for name, e in err.items()
                if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]]

    def _say_expert_load(self, params, key, say):
        """Counters, not metrics and not judged: tokens an expert on a
        seeded batch of the cell's shape, from the program's own router
        (a layer's loads sum to tokens x K by construction)."""
        from horovod_tpu.models import llama_expert_load

        c = self.cfg
        tokens = self.batch(key)["tokens"]
        load = np.asarray(jax.jit(
            lambda p, t: llama_expert_load(p, t, c))(params, tokens))
        say(event="expert_load", tokens=tokens.size,
            routed_slots_per_layer=load.sum(-1).tolist(),
            max_over_mean_per_layer=(load.max(-1) / load.mean(-1)).tolist(),
            min_over_mean_per_layer=(load.min(-1) / load.mean(-1)).tolist())

    def _check_against_reference(self, params, key, say):
        c = self.cfg
        batch = _batch(key, 1, REFERENCE_TOKENS, c.vocab_size)
        ref = reference_readings(reference_params(params), batch, c)
        got = program_readings(params, dict(batch, mask=ref["mask"]), c)
        err, seen = compare_readings(got, ref)
        tol = {"logits": LOGITS_TOL, "logits_decided_tokens": DECIDED_TOL,
               "loss": LOSS_TOL}
        say(event="program_vs_reference", tokens=REFERENCE_TOKENS,
            layer=CHECKED_LAYER, routing_margin=ROUTING_MARGIN, err=err,
            tol=tol, grad_tol=GRAD_TOL, not_judged=seen,
            loss=float(got["loss"]), reference_loss=float(ref["loss"]))
        return [f"{name} error {e} vs the float32 reference"
                for name, e in err.items()
                if not e <= tol.get(name, GRAD_TOL)]


def reference_readings(ref_p, batch, c):
    """What the comparison reads of the reference on ``batch``: logits,
    the mask of the tokens whose routing it decides by more than
    ``ROUTING_MARGIN`` at every layer, and over THOSE tokens the loss
    (plus the aux term, which has no mask) and its gradients in the
    checked layer's leaves."""
    logits, _, margin = jax.jit(
        lambda p, t: reference_forward(p, t, c))(ref_p, batch["tokens"])
    batch = dict(batch, mask=(margin > ROUTING_MARGIN).astype(F32))

    def loss_in_one_layer(lp, p, b):
        layers = list(p["layers"])
        layers[CHECKED_LAYER] = lp
        return reference_loss(dict(p, layers=layers), b, c,
                              c.moe_aux_weight)

    loss, grads = jax.jit(jax.value_and_grad(loss_in_one_layer))(
        ref_p["layers"][CHECKED_LAYER], ref_p, batch)
    return {"logits": logits, "mask": batch["mask"], "loss": loss,
            "grads": {name: grads[name] for name in CHECKED_LEAVES}}


def program_readings(params, batch, c):
    """The same of the program; ``batch`` carries the reference's mask."""
    from horovod_tpu.models import llama_forward, llama_loss

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: llama_loss(p, b, c)))(params, batch)
    grads = {name: grads["layers"][name][CHECKED_LAYER]
             for name in CHECKED_LEAVES}
    logits = jax.jit(lambda p, t: llama_forward(p, t, c))(
        params, batch["tokens"])
    return {"logits": logits, "loss": loss, "grads": grads}


def compare_readings(got, ref):
    """-> (errors that are judged, readings that are only printed)."""
    decided = ref["mask"][..., None] > 0
    err = {"logits": l2_err(got["logits"], ref["logits"]),
           "logits_decided_tokens": rel_err(
               jnp.where(decided, got["logits"], ref["logits"]),
               ref["logits"]),
           "loss": abs(float(got["loss"]) - float(ref["loss"]))
           / abs(float(ref["loss"]))}
    seen = {"logits_max_abs": rel_err(got["logits"], ref["logits"]),
            "decided_tokens": int(ref["mask"].sum())}
    for name, r in ref["grads"].items():
        err["d_" + name] = l2_err(got["grads"][name], r)
        seen["d_" + name + "_max_abs"] = rel_err(got["grads"][name], r)
    return err, seen


def check_grouped_mm(key, rows, k, n, experts, name, say):
    """The program's ``_grouped_mm`` at ``[rows, k] x [experts, k, n]``
    with uneven groups (each row's expert drawn uniformly), forward,
    ``dlhs`` and ``tgmm``, against float32 numpy matmuls on the rows of
    the first, a middle and the last group: a group's rows see one
    expert's matrix and nothing else, so a whole group is a slice the
    host can hold that a wrong tile, offset or clamp cannot pass."""
    from horovod_tpu.ops.grouped_moe import _grouped_mm

    ks = jax.random.split(key, 4)
    lhs = jax.random.normal(ks[0], (rows, k), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (experts, k, n), jnp.bfloat16) \
        * (k ** -0.5)
    cot = jax.random.normal(ks[2], (rows, n), jnp.bfloat16)
    sizes = jnp.bincount(jax.random.randint(ks[3], (rows,), 0, experts),
                         length=experts).astype(jnp.int32)

    def run(lhs, rhs, cot, sizes):
        out, vjp = jax.vjp(lambda a, b: _grouped_mm(a, b, sizes), lhs, rhs)
        return (out,) + vjp(cot)

    out, dlhs, drhs = jax.jit(run)(lhs, rhs, cot, sizes)
    ends = np.cumsum(np.asarray(sizes))
    err = {"fwd": 0.0, "dlhs": 0.0, "tgmm": 0.0}
    for e in (0, experts // 2, experts - 1):
        rows_e = slice(int(ends[e] - sizes[e]), int(ends[e]))
        a, g = (np.asarray(x[rows_e], np.float32) for x in (lhs, cot))
        w = np.asarray(rhs[e], np.float32)
        for what, got, ref in (("fwd", out[rows_e], a @ w),
                               ("dlhs", dlhs[rows_e], g @ w.T),
                               ("tgmm", drhs[e], a.T @ g)):
            got = np.asarray(got, np.float32)
            err[what] = max(err[what], float(
                np.max(np.abs(got - ref)) / np.max(np.abs(ref))))
    say(event="grouped_mm_vs_numpy", which=name,
        shape=[[rows, k], [experts, k, n]],
        group_rows_min_max=[int(sizes.min()), int(sizes.max())],
        err=err, tol=GMM_TOL)
    return [f"grouped GEMM {name} {what} error {e} vs numpy"
            for what, e in err.items() if not e <= GMM_TOL]
