"""Model adapter for kind "afmoe": Trinity-Mini's decoder (window and
full attention layers mixed, a leading dense layer, expert layers with a
shared expert and sigmoid routing) as ONE chip of the eight that share
each layer holds it: experts ``first_expert .. + num_experts - 1`` of the
published 128, a slice of the vocabulary, every head. Run through the
program's own ``LlamaConfig`` / ``llama_init`` / ``llama_loss`` with the
grouped dispatch, exactly the path kinds "lm" and "olmoe" take (whose
adapters this one extends and borrows from). Nothing of the model is
re-implemented here except the plain float32 reference that ``correct``
is decided against: the benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape, WITH the window and
   without, against an explicit-mask float32 attention computed in
   blocks of query rows (so that it fits at T = 8192), forward and
   gradients;
2. the grouped GEMM at the cell's shapes: the static row bound the share
   moves rows for, uneven groups that cover about half of it (the rest
   is rows of experts held elsewhere, which no group covers), forward
   and both backward directions, against float32 ``numpy`` matmuls on
   whole groups;
3. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with: the step the lane builds
   (``make_split_train_step`` on the adapter's loss and optimizer, the
   lane's compiler options: the compile cache hands back the window's
   own executables) gives a loss and the parameters after the step, its
   grad program the gradients. Against the reference on the same
   weights and the same 2 x 8192 tokens, computed a layer at a time and
   in blocks: the loss; EVERY gradient leaf (l2); and the norm of every
   leaf's change under the reference's own first Adam step.

Printed and not judged (``expert_load``): the rows the router hands the
experts held here on the batch the run trained on, a layer, counted by
the reference's router on the weights the run ended with (float32; the
program's bf16 router differs in the tokens whose choice lies within
rounding of an edge), beside the share an even router would hand this
chip and the rows of one chunk of the share's row movement; the grouped
GEMMs' required work is counted from them.
That no held slot is dropped is what (2) and (3) hold the program to:
the reference computes every held expert for every token.

The control (``python3 -m chipbench.models.afmoe --seed N``): the same
run with the REFERENCE computed in fp8 put in the program's place in
all three comparisons, through the same verdicts; it has to come out
not correct in each.
"""

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts
from chipbench.models import lm

F32 = jnp.float32

# published config.json key -> LlamaConfig field (``num_experts`` is the
# experts HELD; the published count is in ``reduced``)
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
         "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
         "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
         "num_experts": "n_experts_held",
         "num_experts_per_tok": "n_experts_per_token",
         "num_dense_layers": "n_dense_layers",
         "num_shared_experts": "n_shared_experts",
         "sliding_window": "sliding_window", "score_func": "score_func",
         "route_norm": "norm_topk_prob", "route_scale": "route_scale",
         "mup_enabled": "scale_embed"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 32 after review; PERF.md section 2): the largest the
# PROGRAM read over its seeds (nine: five in one process ten steps into
# the batch, four whole runs from the committed files), and what the
# REFERENCE reads in the program's place with its matrices (for the
# kernels, its operands) rounded to fp8 (e4m3, the nearest precision
# below the configuration's bf16), which has to fail
# (``Fp8InTheProgramsPlace``; one seed on the chip).
# Flash, bf16 operands, max-abs error over the largest entry; forward,
# backward. Program 0.0029-0.0040 / 0.0028-0.0054 (with the 13 runs
# before review); fp8 0.055-0.060 / dq, dk 0.047-0.056, dv 0.019-0.028.
KERNEL_TOL = {"fwd": 1.5e-2, "bwd": 1.5e-2}
ATTENTION_BLOCK_ROWS = 512
# Grouped GEMM, the same statistic over whole groups. Program
# 0.0021-0.0034 (one bf16 rounding of the result); fp8 0.039-0.045.
GMM_TOL = 1e-2
# The step. Loss and the norm of a leaf's change are hardly moved by the
# precision (at these weights the loss is ln(vocab) in any precision,
# and Adam's first step is lr * sign(gradient)): each three times the
# program's largest reading alone. Loss: program 2.2e-6 - 4.6e-5 (fp8
# 1.0e-4: not told apart). Change: program at most 1.1e-3 (the router,
# 2.4e-4 - 1.1e-3; every other leaf under 2e-4, and 0 where a gain of
# 1.0 in bf16 cannot move by 1e-5, on both sides; fp8 at most 1.6e-3).
LOSS_TOL = 1.4e-4
MOVED_TOL = 3.3e-3
# A gradient leaf's l2 error lies between the program's and the fp8
# reference's. Program at most 0.084 (a norm's gain; the matrices
# 0.046-0.083, the head 0.034-0.040, ``final_norm`` 0.015-0.034); fp8
# 0.166 in the head, 0.23-0.31 in the layers' leaves (``final_norm``
# alone reads 0.086 and passes).
GRAD_TOL = 0.12
# The leaves the ROUTING reaches have a bound of their own: a token
# whose choice lies within bf16's rounding of an edge hands a whole row
# to another expert, and at the timed size every token is in (no mask
# of decided tokens). Program: the held experts' matrices 0.167-0.186,
# the router 0.173-0.233; fp8 0.53 and 0.67.
ROUTED_GRAD_TOL = 0.35
ROUTED_LEAVES = ("router", "moe_gate", "moe_up", "moe_down")
# Tokens a block of the reference's FFNs and of its head.
TOKEN_BLOCK = 2048


# ---------------------------------------------------------------------
# The plain reference. Straightforward float32 jax.numpy under "highest"
# matmul precision: explicit masks, a Python loop over layers, every held
# expert computed for every token and weighted (zero where not chosen),
# the K choices by K arg-maxes; no kernel, no sort, nothing imported from
# the program. Follows Hugging Face's modeling_afmoe.py (the equations:
# horovod_tpu/models/reference.py). Departures: no router aux loss
# (Hugging Face's forward returns none); ``expert_bias`` is read as data;
# bf16-stored parameters are read as float32 (exact).
# So that it fits at the cell's 2 x 8192 tokens the SAME math runs in
# blocks: attention a block of query rows at a time against every key,
# the per-token FFNs and the head a block of tokens at a time
# (``lax.map`` over blocks, a block recomputed in the backward pass), and
# the gradients a layer at a time (``reference_loss_and_grads``). One
# block is the whole.
# ---------------------------------------------------------------------

def _block(n, want):
    """The largest block of at most ``want`` that divides ``n``."""
    return next(r for r in range(min(want, n), 0, -1) if n % r == 0)


def _over_blocks(f, xs, rows, *consts):
    """``f(block of xs, *consts)`` over blocks of ``rows`` leading
    entries of each array in ``xs``, one after another and recomputed
    in the backward pass -> the results stacked, a block an entry."""
    n = jax.tree.leaves(xs)[0].shape[0]
    cut = jax.tree.map(lambda x: x.reshape(n // rows, rows, *x.shape[1:]),
                       xs)
    return jax.lax.map(lambda b: jax.checkpoint(f)(b, *consts), cut)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attend(q, k, v, window):
    """softmax(q k / sqrt(d)) v of float32 ``q`` [B,T,H,D], ``k``, ``v``
    [B,T,Hkv,D] under the explicit mask ``j <= i`` and ``j > i -
    window`` (``window`` 0: no window; a number or a traced scalar, so
    that one program serves both kinds of layer): a block's scores
    against every key are [B,H,rows,T] (1 GB at the cell's shape, where
    all rows at once are 17 GB)."""
    b, t, h, hd = q.shape
    rep = h // k.shape[2]
    rows = _block(t, ATTENTION_BLOCK_ROWS)
    window = jnp.where(window > 0, window, t)

    def block(first, q, k, v, window):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        kk, vv = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kk) / (hd ** 0.5)
        i = first + jnp.arange(rows)[:, None]
        j = jnp.arange(t)[None, :]
        p = jax.nn.softmax(
            jnp.where((j <= i) & (j > i - window), s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    out = jax.lax.map(lambda r: jax.checkpoint(block)(r, q, k, v, window),
                      jnp.arange(0, t, rows))        # [T/rows,B,rows,H,D]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, hd)


def _routed_and_shared(h, lp, c):
    """The expert layer's FFN on tokens ``h`` [n, D] -> (y [n, D], the
    tokens that chose each expert HELD here [held])."""
    n, k_top = c.n_experts, c.n_experts_per_token
    first, held = c.first_expert, c.n_experts_held or c.n_experts
    s = jax.nn.sigmoid(h @ lp["router"])                     # [n, E]
    left, chosen = s + lp["expert_bias"], jnp.zeros_like(s)
    for _ in range(k_top):
        pick = jax.nn.one_hot(jnp.argmax(left, -1), n, dtype=F32)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = chosen * s
    w = c.route_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, lp["moe_gate"])) \
        * jnp.einsum("td,edf->tef", h, lp["moe_up"])
    y = jnp.einsum("tef,efd->ted", act, lp["moe_down"])
    y = jnp.einsum("te,ted->td", w[:, first:first + held], y) \
        + _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return y, jnp.sum(chosen[:, first:first + held], 0)


def reference_layer(lp, x, c, dense, sliding):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: a ``dense`` FFN or the expert layer; ``sliding``
    (a bool, or a traced one: one program for both) says whether it is a
    ``sliding_attention`` layer, with RoPE and the window, or a
    ``full_attention`` one, with neither. -> (x, the tokens that chose
    each held expert [held]; zeros for a dense layer)."""
    hd = c.head_dim
    b, t, d = x.shape
    held = c.n_experts_held or c.n_experts
    with jax.default_matmul_precision("highest"):
        h = _rms(x, lp["attn_norm"], c.norm_eps)
        q = _rms((h @ lp["wq"]).reshape(b, t, c.n_heads, hd),
                 lp["q_norm"], c.norm_eps)
        k = _rms((h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd),
                 lp["k_norm"], c.norm_eps)
        v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
        inv = c.rope_theta ** (-jnp.arange(0, hd // 2, dtype=F32)
                               / (hd // 2))
        ang = jnp.arange(t, dtype=F32)[:, None] * inv        # [T, hd/2]
        cos, sin = (f(ang)[None, :, None] for f in (jnp.cos, jnp.sin))

        def rope(x):
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            return jnp.where(sliding, jnp.concatenate(
                [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1), x)

        a = _attend(rope(q), rope(k), v,
                    jnp.where(sliding, c.sliding_window, 0))
        a = (a.reshape(b, t, -1) * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]
        x = x + _rms(a, lp["post_attn_norm"], c.norm_eps)

        h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
        rows = _block(b * t, TOKEN_BLOCK)
        if dense:
            y = _over_blocks(lambda h, lp: _swiglu(
                h, lp["w_gate"], lp["w_up"], lp["w_down"]), h, rows, lp)
            load = jnp.zeros((held,), F32)
        else:
            y, load = _over_blocks(
                lambda h, lp: _routed_and_shared(h, lp, c), h, rows, lp)
            load = jnp.sum(load, 0)
        x = x + _rms(y.reshape(b, t, d), lp["post_mlp_norm"], c.norm_eps)
    return x, load


def _kind(c, at):
    """Layer ``at`` -> (dense?, sliding?)."""
    return at < c.n_dense_layers, c.layer_types[at] == "sliding_attention"


def _head_loss(final_norm, lm_head, x, targets, eps):
    """Mean cross-entropy over the vocabulary rows held, of ``x``
    [B,T,D] against ``targets`` [B,T]; no aux term."""
    n = targets.size
    rows = _block(n, TOKEN_BLOCK)

    def nll(xt, final_norm, lm_head):
        x, target = xt
        logp = jax.nn.log_softmax(_rms(x, final_norm, eps) @ lm_head, -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_over_blocks(
            nll, (x.reshape(n, -1), targets.reshape(n)), rows, final_norm,
            lm_head))


def _embed(embed, tokens, c):
    return embed[tokens] * (c.d_model ** 0.5)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype``, in ``dtype``. The barrier keeps the
    rounding: the chip's compiler, allowed excess precision, takes a
    conversion to a narrower type and back for no operation (my chip
    runs, PR 32: a bf16 parameter "moved" by an update of a hundredth of
    its spacing)."""
    return jax.lax.optimization_barrier(x.astype(dtype))


def _through(round_to):
    """float32 <- the program's storage dtype; with ``round_to``, every
    matrix by way of that dtype (the control's fp8)."""
    def read(w):
        if round_to is not None and w.ndim >= 2:
            w = _rounded(w.astype(F32), round_to)
        return w.astype(F32)
    return read


def _layers_of(params):
    """The program's parameter tree -> [(stack name, index in the
    stack)] in the model's order: ``dense_layers``, then ``layers``."""
    return [(stack, i) for stack in ("dense_layers", "layers")
            if stack in params
            for i in range(jax.tree.leaves(params[stack])[0].shape[0])]


def reference_params(params):
    """The program's parameter tree (``dense_layers`` and ``layers``
    stacked, any storage dtype) -> float32, one dict a layer, in order."""
    f32 = jax.tree.map(lambda w: w.astype(F32), params)
    out = {k: v for k, v in f32.items()
           if k not in ("layers", "dense_layers")}
    out["layers"] = [jax.tree.map(lambda w: w[i], f32[stack])
                     for stack, i in _layers_of(params)]
    return out


def reference_forward(p, tokens, c):
    """``p`` from :func:`reference_params`; tokens [B, T] -> the hidden
    state the head reads [B, T, D]."""
    x = _embed(p["embed"], tokens, c)
    for at, lp in enumerate(p["layers"]):
        x, _ = reference_layer(lp, x, c, *_kind(c, at))
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
    once a process. ``layer[dense]``: ONE program for the dense layers
    and one for the expert layers, whatever the depth and the pattern
    (``sliding`` is traced): the layer, the tokens that chose each held
    expert, and its VJP under ``dy``. The forward sweep runs it too,
    with a zero ``dy`` and its gradients dropped: a layer twice more on
    the chip costs less than a second program's compilation."""
    def layer(dense):
        def run(lp, x, dy, sliding):
            y, vjp, load = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, dense, sliding),
                lp, x, has_aux=True)
            return y, load, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer={dense: layer(dense) for dense in (True, False)},
        embed=jax.jit(lambda e, t: _embed(e, t, c)),
        head=jax.jit(jax.value_and_grad(
            lambda g, w, x, t: _head_loss(g, w, x, t, c.norm_eps),
            argnums=(0, 1, 2))),
        d_embed=jax.jit(lambda dx, t: jnp.zeros(
            (c.vocab_size, c.d_model), F32).at[t].add(
                dx * (c.d_model ** 0.5))))


@functools.lru_cache(maxsize=None)
def _unstack(round_to):
    """(a stack of layers, i) -> layer i's leaves as :func:`_through`
    reads them; one program a stack."""
    read = _through(round_to)
    return jax.jit(lambda stack, i: jax.tree.map(
        lambda w: read(jax.lax.dynamic_index_in_dim(w, i, keepdims=False)),
        stack))


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` and its gradient in every leaf
    of ``params`` (the program's tree), a layer at a time: forward
    keeping each layer's input, then the head, then the layers from the
    last to the first, each recomputed under ``jax.vjp``. ``visit(where,
    grads)`` is handed each set of float32 gradients as it is known
    (``where``: ``()`` for the top level's leaves, else (stack, index));
    nothing of them is kept here, so the whole fits beside the program's
    own gradients. -> (loss, [tokens that chose each held expert, an
    expert layer])."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    where = _layers_of(params)

    def layer(at):
        stack, i = where[at]
        return _unstack(round_to)(params[stack], i)

    x = run.embed(read(params["embed"]), tokens)
    inputs, loads, no_dy = [], [], jnp.zeros_like(x)
    for at in range(c.n_layers):
        dense, sliding = _kind(c, at)
        inputs.append(x)
        x, load, _ = run.layer[dense](layer(at), x, no_dy, sliding)
        if not dense:
            loads.append(load)
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), read(params["lm_head"]), x,
        batch["targets"])
    del x
    visit((), {"final_norm": d_norm, "lm_head": d_head})
    del d_norm, d_head
    for at in reversed(range(c.n_layers)):
        dense, sliding = _kind(c, at)
        _, _, (d_lp, dx) = run.layer[dense](layer(at), inputs.pop(), dx,
                                            sliding)
        visit(where[at], d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(dx, tokens)})
    return loss, loads


def _attend_weighted(q, k, v, w, window):
    with jax.default_matmul_precision("highest"):
        out = _attend(q, k, v, window)
    return jnp.sum(out * w), out


@jax.jit
def reference_attention(q, k, v, w, window):
    """Explicit-mask float32 attention of ``q`` [B,T,H,D], ``k``, ``v``
    [B,T,Hkv,D] (any dtype, read as float32) and the gradients of
    ``sum(out * w)``, in blocks of query rows (:func:`_attend`);
    ``window`` 0: none. -> (out, dq, dk, dv), float32."""
    grads, out = jax.grad(_attend_weighted, argnums=(0, 1, 2),
                          has_aux=True)(
        *(x.astype(F32) for x in (q, k, v, w)), window)
    return (out,) + grads


def adam_first_step(p, g, opt):
    """What Adam's first step from a zero state makes of parameter ``p``
    (storage dtype kept) under the float32 gradient ``g``: the bias
    corrections cancel the decays, so the moments are ``g`` and ``g^2``
    and the update is ``-lr * g / (|g| + eps)``. Written out here, not
    taken from the optimizer the program uses."""
    assert opt["name"] == "adam", opt
    u = -opt["learning_rate"] * g / (jnp.abs(g) + opt.get("eps", 1e-8))
    return _rounded(p.astype(F32) + u, p.dtype)


# ---------------------------------------------------------------------

class Model(lm.Model):
    """Kind "lm"'s adapter (init, loss, optimizer through the program's
    llama functions) with Trinity-Mini's configuration and share, its
    counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            n_experts=config["reduced"]["num_experts"]["published"],
            first_expert=a["first_expert"],
            layer_types=tuple(config["layer_types"]),
            qk_norm="head", attn_gate=True, post_norm=True,
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

    def batch(self, key):
        """Kind "lm"'s batch (ids from the vocabulary rows held), and
        the adapter keeps the tokens: the step that is compared, and the
        rows held, are on the batch the run trained on."""
        batch = super().batch(key)
        jax.debug.callback(self._keep, batch["tokens"])
        return batch

    def _keep(self, tokens):
        self.trained_on = np.asarray(tokens)

    # -- counts ---------------------------------------------------------

    @property
    def even_share(self):
        """Rows an even router hands this chip a layer and step."""
        c = self.cfg
        return self.units_per_step * c.n_experts_per_token \
            * c.n_experts_held // c.n_experts

    def _rows(self):
        n = self.cfg.n_layers - self.cfg.n_dense_layers
        return self.rows_held or [self.even_share] * n

    def _windows(self):
        return [w for _, w, _ in self.cfg.layer_kinds()]

    def flops_per_unit(self):
        c = self.cfg
        rows = self._rows()
        params = afmoe_counts.matmul_params_per_token(
            c.d_model, c.d_ff, c.expert_width, c.n_heads, c.n_kv_heads,
            c.head_dim, c.n_dense_layers, len(rows), c.vocab_size,
            c.n_experts, c.n_shared_experts,
            sum(rows) / len(rows) / self.units_per_step)
        return afmoe_counts.train_flops_per_token(
            params, self.seq, c.n_heads, c.head_dim, self._windows())

    def grouped_gemm_work(self):
        """(required FLOPs, required bytes) of the grouped GEMMs a step,
        for the rows HELD: what ``moe_gmm_roofline_pct`` divides by the
        kernels' time."""
        c = self.cfg
        rows = self._rows()
        return (afmoe_counts.grouped_gemm_flops(rows, c.d_model,
                                                c.expert_width),
                afmoe_counts.grouped_gemm_bytes(
                    rows, c.d_model, c.expert_width, c.n_experts_held,
                    jnp.dtype(c.compute_dtype).itemsize))

    def flash_window_work(self):
        """(required FLOPs, required bytes) of the window layers' flash
        calls a step: ``flash_window_roofline_pct``'s numerator."""
        c = self.cfg
        layers = [w for w in self._windows() if w]
        return (sum(afmoe_counts.attention_flops(
                    self.batch_size, self.seq, c.n_heads, c.head_dim, w)
                    for w in layers),
                len(layers) * afmoe_counts.attention_bytes(
                    self.batch_size, self.seq, c.n_heads, c.n_kv_heads,
                    c.head_dim, jnp.dtype(c.compute_dtype).itemsize))

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the flash forward kernel WITH a
        window and without, and megablox's grouped GEMMs (jitted ``gmm``
        and ``tgmm``), not their reference branches."""
        if not on_tpu:
            return None
        flash = [m for m in re.findall(r"kernel_metadata = \"\{[^}]*\}",
                                       text) if "hvd_flash_fwd" in m]
        missing = [name for name, there in (
            ("tpu_custom_call", "tpu_custom_call" in text),
            ("hvd_flash_fwd with a window",
             any("window" in m for m in flash)),
            ("hvd_flash_fwd without a window",
             any("window" not in m for m in flash)),
            ("@gmm", "@gmm" in text), ("@tgmm", "@tgmm" in text))
            if not there]
        if missing:
            return f"grad program lowered without {missing}: a " \
                   "kernel's reference branch ran"
        return None

    def check_outputs(self, params, key, say):
        """Returns a list of faults (empty = correct); see the module
        docstring for what is compared. The timed programs come back
        from the compile cache; everything ELSE compiled here stays out
        of it: those programs run once, after the window, and with
        whole-model check programs in it this cell's entries passed what
        the machine's capped cache holds, so that every run evicted the
        grad program and no run was warm (my chip runs, PR 32)."""
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
            faults = []
            for k, window in zip(ks[:2], (c.sliding_window, 0)):
                faults += self._check_flash(k, window, say)
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

    def row_bound(self):
        """The rows of one chunk of the share's row movement: what the
        layer moves at any load up to that
        (``ops/grouped_moe.py:_held_experts_ffn``)."""
        from horovod_tpu.ops import grouped_moe

        c = self.cfg
        slots = self.units_per_step * c.n_experts_per_token
        chunks = max(c.n_experts // (c.n_experts_held
                                     * grouped_moe._HELD_ROW_BOUND), 1)
        return slots // chunks if slots % chunks == 0 else slots

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _flash(self, q, k, v, w, window):
        """-> (out, dq, dk, dv) of ``sum(out * w)``."""
        return _program_flash(q, k, v, w, window)

    def _grouped_mm(self, lhs, rhs, cot, sizes):
        """-> (out, d lhs, d rhs) under the cotangent ``cot``."""
        return _program_grouped_mm(lhs, rhs, cot, sizes)

    def _step_readings(self, params, batch, say):
        """One more step of the TIMED programs on ``batch`` from
        ``params`` and a fresh optimizer state -> its loss, the grad
        program's gradients, the parameters after it. Built as the lane
        builds them (``lanes/spmd.py``): the same loss, optimizer and
        compiler options through ``make_split_train_step``, whose grad
        program is ``value_and_grad`` of the loss under the name
        ``hvd_grad``; the compile cache answers with the window's own
        executables (``cache`` on the line: misses stay as they were)."""
        from horovod_tpu.parallel import make_split_train_step

        on_tpu = jax.local_devices()[0].platform == "tpu"
        jk = {"compiler_options": self.compiler_options} \
            if on_tpu and self.compiler_options else {}

        def loss_fn(params, batch):
            return self.loss(params, (), batch)[0]

        def hvd_grad(p, d):
            return jax.value_and_grad(loss_fn)(p, d)

        before = _cache_counts()
        ts = make_split_train_step(loss_fn, self.optimizer(1),
                                   jit_kwargs=jk)
        # The apply program donates what it is given: a copy. The step
        # first, while nothing else is held: beside the copy it holds
        # what a step of the window holds.
        copy = jax.jit(lambda p: jax.tree.map(jnp.copy, p))
        loss, (after, opt) = ts.step(ts.init(copy(params)), batch)
        del opt
        _, grads = jax.jit(hvd_grad, **jk)(params, batch)
        jax.block_until_ready((grads, after))
        say(event="timed_programs_once_more",
            tokens=int(batch["tokens"].size), cache_before=before,
            cache=_cache_counts())
        return {"loss": loss, "grads": grads, "after": after}

    def _check_flash(self, key, window, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        kv = (self.batch_size, self.seq, c.n_kv_heads, c.head_dim)
        q, k, v, w = _normal(key, (shape, kv, kv, shape))
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._flash(q, k, v, w, window),
            reference_attention(q, k, v, w, window)))))
        say(event="flash_vs_explicit_mask", shape=list(shape),
            window=window,
            block_rows=_block(self.seq, ATTENTION_BLOCK_ROWS),
            err=err, tol=KERNEL_TOL)
        return [f"flash (window {window}) {name} error {e} vs the "
                "explicit mask" for name, e in err.items()
                if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]]

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

    def _say_expert_load(self, load, batch, say):
        """Counters, not judged: the rows the router hands the experts
        held here, a layer (``load`` [expert layers, held], from the
        reference's router on the weights the run ended with). The
        grouped GEMMs' required work is counted from them."""
        self.rows_held = [int(x) for x in load.sum(-1)]
        bound = self.row_bound()
        say(event="expert_load", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", router="the reference's, float32",
            rows_held_per_layer=self.rows_held,
            rows_an_even_router_hands_this_chip=self.even_share,
            row_bound=bound,
            layers_over_the_bound=sum(r > bound for r in self.rows_held),
            held_max_over_mean_per_layer=(load.max(-1)
                                          / load.mean(-1)).tolist(),
            held_min_over_mean_per_layer=(load.min(-1)
                                          / load.mean(-1)).tolist())


def _bound(reading):
    """The bound of a reading of ``step_vs_reference``."""
    if reading == "loss":
        return LOSS_TOL
    kind, leaf = reading.split("_", 1)
    if kind == "moved":
        return MOVED_TOL
    return ROUTED_GRAD_TOL if leaf in ROUTED_LEAVES else GRAD_TOL


def _cache_counts():
    """This process's compile-cache hits and misses so far, by the
    program's counter; None where the program has none."""
    try:
        from horovod_tpu.utils.compile_cache import compile_stats
    except ImportError:
        return None
    stats = compile_stats()
    return {k: stats[k] for k in ("cache_hits", "cache_misses")}


# The checks' glue is jitted too: on the chip every operation done one
# at a time is a program compiled for it.

@functools.partial(jax.jit, static_argnames="shapes")
def _normal(key, shapes):
    """Standard normal bf16 operands of ``shapes``."""
    return tuple(jax.random.normal(k, shape, jnp.bfloat16) for k, shape
                 in zip(jax.random.split(key, len(shapes)), shapes))


@jax.jit
def _rel_errs(got, ref):
    """``compare.rel_err`` of each pair: max |got - ref| / max |ref|."""
    return tuple(jnp.max(jnp.abs(g.astype(F32) - r.astype(F32)))
                 / jnp.max(jnp.abs(r.astype(F32)))
                 for g, r in zip(got, ref))


@functools.partial(jax.jit, static_argnames=("rows", "covered", "k", "n",
                                             "experts"))
def _grouped_operands(key, rows, covered, k, n, experts):
    """-> (lhs [rows, k], rhs [experts, k, n], cot [rows, n], sizes
    [experts]: ``covered`` rows' experts drawn uniformly)."""
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (rows, k), jnp.bfloat16),
            jax.random.normal(ks[1], (experts, k, n), jnp.bfloat16)
            * (k ** -0.5),
            jax.random.normal(ks[2], (rows, n), jnp.bfloat16),
            jnp.bincount(jax.random.randint(ks[3], (min(covered, rows),),
                                            0, experts),
                         length=experts).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames="window")
def _program_flash(q, k, v, w, window):
    from horovod_tpu.ops import flash_attention

    def f(q, k, v, w):   # w rides as an argument, never closed over
        out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    got, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v, w)
    return (out,) + got


@jax.jit
def _program_grouped_mm(lhs, rhs, cot, sizes):
    from horovod_tpu.ops.grouped_moe import _grouped_mm

    out, vjp = jax.vjp(lambda a, b: _grouped_mm(a, b, sizes), lhs, rhs)
    return (out,) + vjp(cot)


@jax.jit
def _leaves_readings(g, p, after, r, i, lr, eps):
    """:func:`_leaf_readings` of every leaf of the dict ``r``: one
    program a kind of layer."""
    return {name: _leaf_readings(g[name], p[name], after[name], r[name],
                                 i, lr, eps) for name in r}


def _leaf_readings(g, p, after, r, i, lr, eps):
    """One leaf of the step against the reference's gradient ``r`` of
    it: ``g`` the program's gradient, ``p`` the parameter, ``after`` the
    parameter after the step (entry ``i`` of each where the leaf is one
    layer's of a stack) -> ``d``: the l2 error of ``g``; ``moved``: the
    norm of ``after - p`` against that of Adam's first step under ``r``,
    as a share of it. Where no gradient reaches a leaf
    (``expert_bias``), none may in the program either, and where the
    reference's step leaves a leaf as it is (a gain of 1.0 in bf16 under
    a small rate), so must the program's: 0 then, else infinity."""
    if i is not None:
        g, p, after = (jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
                       for x in (g, p, after))
    g, p32, after = (x.astype(F32) for x in (g, p, after))
    norm = jnp.linalg.norm

    def share(off, of):
        return jnp.where(of > 0, off / of, jnp.where(off > 0, jnp.inf, 0.0))

    should = norm(adam_first_step(p, r, {"name": "adam", "eps": eps,
                                         "learning_rate": lr})
                  .astype(F32) - p32)
    return {"d": share(norm(g - r), norm(r)),
            "moved": share(jnp.abs(norm(after - p32) - should), should)}


def check_grouped_mm(key, rows, covered, k, n, experts, name, say, run):
    """``run`` (the program's ``_grouped_mm`` and its VJP:
    ``Model._grouped_mm``) at ``[rows, k] x [experts, k, n]`` as the
    share calls it: uneven groups (``covered`` rows, each row's expert
    drawn uniformly) at the head of ``rows``, the rest covered by NO
    group. Forward, ``dlhs`` and ``tgmm`` against float32 numpy matmuls
    on the rows of the first, a middle and the last group (kind
    "olmoe"'s comparison): a wrong tile, offset or clamp cannot pass a
    whole group, and a kernel that read a row past the groups into
    ``tgmm`` would show in the last."""
    operands = _grouped_operands(key, rows, covered, k, n, experts)
    # to the host whole, once: numpy does the slicing
    lhs, rhs, cot, sizes, out, dlhs, drhs = (
        np.asarray(x) for x in operands + tuple(run(*operands)))
    ends = np.cumsum(sizes)
    err = {"fwd": 0.0, "dlhs": 0.0, "tgmm": 0.0}
    for e in (0, experts // 2, experts - 1):
        rows_e = slice(int(ends[e] - sizes[e]), int(ends[e]))
        a, g = (x[rows_e].astype(np.float32) for x in (lhs, cot))
        w = rhs[e].astype(np.float32)
        for what, got, ref in (("fwd", out[rows_e], a @ w),
                               ("dlhs", dlhs[rows_e], g @ w.T),
                               ("tgmm", drhs[e], a.T @ g)):
            err[what] = max(err[what], float(
                np.max(np.abs(got.astype(np.float32) - ref))
                / np.max(np.abs(ref))))
    say(event="grouped_mm_vs_numpy", which=name,
        shape=[[rows, k], [experts, k, n]], rows_in_groups=int(ends[-1]),
        group_rows_min_max=[int(sizes.min()), int(sizes.max())],
        err=err, tol=GMM_TOL)
    return [f"grouped GEMM {name} {what} error {e} vs numpy"
            for what, e in err.items() if not e <= GMM_TOL]


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

FP8 = jnp.float8_e4m3fn


def _fp8(x):
    return _rounded(x.astype(F32), FP8).astype(F32)


@jax.jit
def _fp8_grouped_mm(lhs, rhs, cot, sizes):
    a, w, g = _fp8(lhs), _fp8(rhs), _fp8(cot)
    row = jnp.arange(a.shape[0])[:, None]
    ends = jnp.cumsum(sizes)[None, :]
    inside = ((row >= ends - sizes[None, :]) & (row < ends)).astype(F32)
    with jax.default_matmul_precision("highest"):
        return (jax.lax.ragged_dot(a, w, sizes),
                jax.lax.ragged_dot(g, jnp.swapaxes(w, 1, 2), sizes),
                jnp.stack([(a * inside[:, e:e + 1]).T @ g
                           for e in range(w.shape[0])]))


class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the three
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3: 3 mantissa bits
    where bf16 has 7), through the same verdicts. Every bound has to
    refuse it."""

    def _flash(self, q, k, v, w, window):
        return reference_attention(_fp8(q), _fp8(k), _fp8(v), w, window)

    def _grouped_mm(self, lhs, rhs, cot, sizes):
        return _fp8_grouped_mm(lhs, rhs, cot, sizes)

    def _step_readings(self, params, batch, say):
        seen = {}
        loss, _ = reference_loss_and_grads(
            params, batch, self.cfg,
            lambda where, ref: seen.setdefault(where, {}).update(ref),
            round_to=FP8)
        grads = seen.pop(())
        for stack in ("dense_layers", "layers"):
            n = len([w for w in seen if w[0] == stack])
            if n:
                grads[stack] = {name: jnp.stack(
                    [seen[stack, i][name] for i in range(n)])
                    for name in params[stack]}
        say(event="the_reference_in_fp8_in_the_programs_place")
        return {"loss": loss, "grads": grads,
                "after": jax.tree.map(
                    lambda p, g: adam_first_step(p, g, self.opt), params,
                    grads)}


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
    _, _, config, traffic = child.find_cell("trinitymini.spmd.b2s8192")
    enable_compile_cache()
    lane = child.load_file("lanes", traffic["lane"]).Lane(traffic)
    lane.start()

    def say(**fields):
        print(json.dumps(fields), flush=True)

    result = child.measure(
        Fp8InTheProgramsPlace(config, traffic), lane, traffic,
        seed=args.seed, seconds=2.0, trace=False, t0=t0, say=say)
    refused = {kind: [f for f in result["faults"] if f.startswith(kind)]
               for kind in ("flash", "grouped GEMM", "the step")}
    say(event="control", fp8_refused_by=refused,
        other_faults=[f for f in result["faults"]
                      if not any(f in fs for fs in refused.values())])
    lane.close()
    return 0 if all(refused.values()) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
