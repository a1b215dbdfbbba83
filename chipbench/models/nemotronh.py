"""Model adapter for kind "nemotronh": NVIDIA-Nemotron-3-Super-120B-A12B's
decoder (layers that are ONE part: a Mamba-2 / SSD mixer, an attention
layer of 32 query heads on 2 key/value heads without position encoding,
or a latent expert layer of 512 experts top-22 behind a 4096 -> 1024
projection with ReLU-squared experts and one shared expert; a
multi-token-prediction module behind the last layer) as ONE chip of the
sixty-four that share each layer holds it: 8 of the 512 experts of each
expert layer, an eighth of the vocabulary, every head of both mixers.
Run through the program's own ``LlamaConfig`` / ``llama_init`` /
``llama_loss``, the path every LM kind takes; this adapter extends kind
"jamba"'s (the step of a state that fills the chip) and through it kind
"afmoe"'s (the batch it keeps, the kernel comparisons' glue, the grouped
GEMM's comparison) and borrows the blocked pieces of their references.
Nothing of the model is re-implemented here except the plain float32
reference that ``correct`` is decided against: the benchmark's own copy
(the program keeps one in ``horovod_tpu/models/reference.py``, which a
later PR may edit; this one it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

1. the flash kernel at the cell's attention shape (heads 128 wide, 32 on
   2) against an explicit-mask float32 attention computed in blocks of
   query rows, forward and gradients;
2. the program's SSD recurrence (``ops/ssd.py``) at [batch, seq, 128
   heads, 64] x 128 states in 8 groups against the recurrence TOKEN BY
   TOKEN in float32, forward and the gradients of ``X``, ``dt``, ``A``,
   ``B``, ``C`` and ``D``;
3. the grouped GEMM at the share's shapes (a chunk of the row movement x
   the latent width x the expert width, and back), uneven groups that
   cover about an even router's rows, the rest covered by no group;
4. ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
   and the weights it ended with, against the reference on the same
   weights and tokens, a part at a time and in blocks (the recurrence a
   sequence at a time, attention by query rows, the expert layer and the
   heads by token blocks): the loss WITH its MTP term, and the MTP term
   alone (the program's loss less the reference's main term, over the
   weight: a dropped or mis-shifted second loss is refused); EVERY
   gradient leaf (l2), the MTP module's included; and the norm of every
   leaf's change under the reference's own first Adam step.

The control (``python3 -m chipbench.models.nemotronh --seed N``): the
same run with the REFERENCE computed in fp8 put in the program's place
in all four comparisons, through the same verdicts; it has to come out
not correct in each.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import afmoe_counts, ssd_counts
from chipbench.models import afmoe, jamba, lm
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
    _through,
    _unstack,
    adam_first_step,
    check_grouped_mm,
)

# published config.json key -> LlamaConfig field (``n_routed_experts`` is
# the experts HELD; the published count is in ``reduced``)
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
         "intermediate_size": "d_ff", "layer_norm_epsilon": "norm_eps",
         "conv_kernel": "conv_taps", "use_conv_bias": "mamba_conv_bias",
         "mamba_num_heads": "ssd_heads", "mamba_head_dim": "ssd_head_dim",
         "ssm_state_size": "ssd_state", "n_groups": "ssd_groups",
         "chunk_size": "ssd_chunk", "n_routed_experts": "n_experts_held",
         "num_experts_per_tok": "n_experts_per_token",
         "moe_intermediate_size": "moe_d_ff",
         "moe_latent_size": "moe_latent",
         "moe_shared_expert_intermediate_size": "shared_d_ff",
         "n_shared_experts": "n_shared_experts",
         "norm_topk_prob": "norm_topk_prob",
         "routed_scaling_factor": "route_scale",
         "num_nextn_predict_layers": "mtp_layers"}
# hybrid_override_pattern's characters -> ``layer_types``
_PARTS = {"M": "mamba2", "*": "full_attention", "E": "experts"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 50; PERF.md section 2): the largest the PROGRAM read over
# six runs on six seeds, and what the REFERENCE reads in the program's
# place with its matrices (for the kernels, its operands) rounded to fp8
# (e4m3, the nearest precision below the configuration's bf16), which
# has to fail (``Fp8InTheProgramsPlace``; one seed).
# Flash at heads 128 wide, 32 on 2, bf16 operands, max-abs error over the
# largest entry; forward, backward. Program 0.0029-0.0034 / 0.0025-0.0050;
# fp8 0.062 / dq 0.047, dk 0.055 (dv 0.0142 passes: the kind is refused
# by the other three, as in kind "jamba").
KERNEL_TOL = {"fwd": 1.5e-2, "bwd": 1.5e-2}
# The SSD recurrence, the same statistic: out, dX, ddt, dA, dB, dC, dD.
# Program: out, dX, dB, dC (rounded to bf16 as they leave, the scores,
# ``dt x`` and the state as they enter a matmul) 0.0024-0.0059, ddt
# 0.0016-0.0020, dA 0.0003-0.0006, dD under 1e-6; fp8 0.049 (dX) - 0.105
# (dA), dD 0.012.
SSD_TOL = 1.5e-2
# Grouped GEMM at [5632, 1024] x [8, 1024, 2688] and back, the same
# statistic over whole groups. Program 0.0021-0.0034 (one bf16 rounding
# of the result); fp8 0.037-0.046.
GMM_TOL = afmoe.GMM_TOL
# The step. Loss (both terms) and the MTP term alone (the program's loss
# less the reference's main term, over the weight): program 5.0e-6 -
# 7.6e-5 both; fp8 5.1 and 5.2 (the fp8 model no longer recites the batch
# the run memorised: loss 4.95 where the reference reads 0.81). The
# limit of kind "lfm2moe", thirteen times the program's largest.
LOSS_TOL = 1e-3
MTP_LOSS_TOL = 1e-3
# A gradient leaf's l2 error, the worst layer. Program: ``wk`` / ``wq``
# 0.047-0.070, the ``ssd_*`` leaves 0.031-0.060, ``shared_*``,
# ``mlp_norm``, ``embed`` 0.038-0.052, the module's attention 0.007-0.053,
# ``lm_head`` 0.025, ``final_norm`` 0.006; fp8 0.28 (``mtp.mlp_norm``),
# 0.56-1.5 in the module's other leaves, 9.8-80 in the model's.
GRAD_TOL = 0.15
# The leaves the ROUTING reaches have a bound of their own, as kind
# "afmoe" has it: a token whose choice lies within bf16's rounding of an
# edge hands a whole row to another expert, here 22 of 512 a token; the
# latent projections stand on the routed rows' path. Program: the router
# 0.226-0.335, the held experts' two matrices and the latent projections
# 0.179-0.204, the module's 0.140-0.170 (its router 0.048-0.062); fp8 0.79
# (``mtp.router``), 1.13-1.18 in the module's, 14.5-30 in the model's.
ROUTED_GRAD_TOL = 0.55
ROUTED_LEAVES = ("router", "moe_up", "moe_down", "moe_lat_down",
                 "moe_lat_up")
# The norm of a leaf's change against that of the reference's own first
# Adam step: hardly moved by the precision (Adam's first step is lr x
# sign(gradient)), so its limit stands between the program's largest and
# 1, which a state left unchanged reads, nearer the former. Program:
# 0-0.0093 (the taps; the router 0.0034; most leaves under 0.001); fp8
# 0.386 (the router), 0.087 and under elsewhere.
MOVED_TOL = 0.2
TOKEN_BLOCK = 1024
# Tokens between two states the reference's recurrence keeps for its
# backward pass (``jax.checkpoint`` a segment): memory, not mathematics.
SEGMENT = 64


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the SSD recurrence TOKEN BY TOKEN
# as it is written (a ``lax.scan`` over tokens: no chunk, no kernel), the
# convolution as explicit shifted products, attention under an explicit
# mask, every held expert computed for every token and weighted (zero
# where not chosen), the K choices by K arg-maxes; nothing imported from
# the program but the rule that says in which stack a layer's parameters
# lie (``LlamaConfig.layer_plan``). Follows Hugging Face's
# modeling_nemotron_h.py, Mamba-2 and DeepSeek-V3's MTP (the equations
# and the departures: horovod_tpu/models/reference.py). So that it fits
# at the cell's 8192 tokens the SAME math runs in blocks, as kinds
# "afmoe" and "jamba" do, and the gradients a part at a time. One block
# is the whole.
# ---------------------------------------------------------------------

def ssd_recurrence(X, dt, A, Bm, Cm, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t; Y_t = S_t C_t + D
    X_t`` from ``S_0 = 0``, token by token, for ``X`` [B, T, H, P],
    ``dt`` [B, T, H], ``A``, ``D`` [H], ``Bm``, ``Cm`` [B, T, G, N] (head
    ``h`` reads group ``h // (H / G)``), float32 -> ``Y`` [B, T, H, P]."""
    b, t, heads, p = X.shape
    rep = heads // Bm.shape[2]

    def token(S, x):
        X, dt, Bt, Ct = x               # [B, H, P], [B, H], [B, G, N]
        Bt, Ct = jnp.repeat(Bt, rep, 1), jnp.repeat(Ct, rep, 1)
        S = jnp.exp(dt * A)[..., None, None] * S \
            + (dt[..., None] * X)[..., None] * Bt[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct) + D[:, None] * X

    seg = _block(t, SEGMENT)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1],
                                             *x.shape[2:])
               for x in (X, dt, Bm, Cm))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(token, S, x)),
        jnp.zeros((b, heads, p, Bm.shape[-1]), F32), xs)
    return jnp.moveaxis(y.reshape(t, b, heads, p), 0, 1)


def mamba2_mixer(h, lp, c):
    """The mamba2 mixer on normalized ``h`` [B, T, D] with one layer's
    float32 parameters (``ssd_in`` columns ``[z | X B C | dt]``)."""
    b, t, _ = h.shape
    H, G, N = c.ssd_heads, c.ssd_groups, c.ssd_state
    di, gn = H * c.ssd_head_dim, G * N
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
    y = ssd_recurrence(
        xbc[..., :di].reshape(b, t, H, -1),
        jax.nn.softplus(r + lp["ssd_dt_bias"]), -jnp.exp(lp["ssd_a_log"]),
        xbc[..., di:di + gn].reshape(b, t, G, N),
        xbc[..., di + gn:].reshape(b, t, G, N), lp["ssd_d"])
    # the gate first, then the norm over each group's channels
    y = (y.reshape(b, t, di) * jax.nn.silu(z)).reshape(b, t, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c.norm_eps)
    return (y.reshape(b, t, di) * lp["ssd_out_norm"]) @ lp["ssd_out"]


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def _routed_and_shared(h, lp, c):
    """The expert layer on tokens ``h`` [n, D] -> (y [n, D], the tokens
    that chose each expert HELD here [held]): the router and the shared
    expert on ``h``, the held experts in the latent space."""
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
    v = h @ lp["moe_lat_down"]
    act = jnp.square(jax.nn.relu(jnp.einsum("tl,elf->tef", v,
                                            lp["moe_up"])))
    y = jnp.einsum("tef,efl->tel", act, lp["moe_down"])
    y = jnp.einsum("te,tel->tl", w[:, first:first + held], y) \
        @ lp["moe_lat_up"] + _relu2(h, lp["shared_up"], lp["shared_down"])
    return y, jnp.sum(chosen[:, first:first + held], 0)


def reference_layer(lp, x, c, mixer):
    """One layer of the model on ``x`` [B,T,D] with its float32
    parameters ``lp``: ONE part under one norm, a ``mixer`` "mamba2" or
    "attention", or (None) the expert layer. -> (x, the tokens that
    chose each held expert [held]; zeros for a mixer)."""
    hd = c.head_dim
    b, t, d = x.shape
    load = jnp.zeros((c.n_experts_held or c.n_experts,), F32)
    with jax.default_matmul_precision("highest"):
        if mixer == "mamba2":
            h = _rms(x, lp["ssd_norm"], c.norm_eps)
            y = _over_blocks(lambda h, lp: mamba2_mixer(h, lp, c), h, 1, lp)
        elif mixer == "attention":
            h = _rms(x, lp["attn_norm"], c.norm_eps)
            q = (h @ lp["wq"]).reshape(b, t, c.n_heads, hd)
            k = (h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, hd)
            y = _attend(q, k, v, 0).reshape(b, t, -1) @ lp["wo"]
        else:
            h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
            y, load = _over_blocks(
                lambda h, lp: _routed_and_shared(h, lp, c), h,
                _block(b * t, TOKEN_BLOCK), lp)
            load = jnp.sum(load, 0)
    return x + y.reshape(b, t, d), load


def _glue(gp, nxt, x, eps):
    """The MTP module's glue: ``[RMS_e(embedding of token t+1) ;
    RMS_h(the stream at t)] W_eh``."""
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_rms(nxt, gp["token_norm"], eps),
             _rms(x, gp["hidden_norm"], eps)], -1) @ gp["eh_proj"]


def _head_loss(final_norm, lm_head, x, targets, mask, eps):
    """Cross-entropy over the vocabulary rows held, of ``x`` [B,T,D]
    against ``targets`` [B,T], its mean over the positions ``mask``
    [B,T] keeps; in blocks of tokens."""
    n = targets.size
    rows = _block(n, TOKEN_BLOCK)

    def nll(xt, final_norm, lm_head):
        x, target = xt
        logp = jax.nn.log_softmax(_rms(x, final_norm, eps) @ lm_head, -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    with jax.default_matmul_precision("highest"):
        nll = _over_blocks(nll, (x.reshape(n, -1), targets.reshape(n)),
                           rows, final_norm, lm_head).reshape(n)
        mask = mask.reshape(n)
        return jnp.sum(nll * mask) / jnp.sum(mask)


def _masks(targets):
    """(every position, the positions that have a token after the
    target: all but a sequence's last), float32 [B, T]."""
    every = jnp.ones(targets.shape, F32)
    return every, every * (jnp.arange(targets.shape[1])
                           < targets.shape[1] - 1)


_GLUE = ("token_norm", "hidden_norm", "eh_proj")


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program a kind of part (mamba2, attention,
    experts), whatever the depth and whether the main model or the MTP
    module runs it: the part, the tokens that chose each held expert and
    its VJP under ``dy``. The forward sweep runs it too, with a zero
    ``dy`` and its gradients dropped (kind "afmoe" says why)."""
    def layer(mixer):
        def run(lp, x, dy):
            y, vjp, load = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, mixer), lp, x,
                has_aux=True)
            return y, load, vjp(dy)
        return jax.jit(run)

    def glue(gp, nxt, x, dy):
        y, vjp = jax.vjp(lambda gp, nxt, x: _glue(gp, nxt, x, c.norm_eps),
                         gp, nxt, x)
        return y, vjp(dy)

    return types.SimpleNamespace(
        layer={mixer: layer(mixer) for mixer in {
            spec.mixer for mtp in (False, True)
            for spec in c.layer_plan(mtp)}},
        embed=jax.jit(lambda e, t: e[t]),
        glue=jax.jit(glue),
        head=jax.jit(jax.value_and_grad(
            lambda g, w, x, t, m: _head_loss(g, w, x, t, m, c.norm_eps),
            argnums=(0, 1, 2))),
        d_embed=jax.jit(lambda dx, t, de, nxt: jnp.zeros(
            (c.vocab_size, c.d_model), F32).at[t].add(dx).at[nxt].add(de)),
        scaled=jax.jit(lambda w, *xs: tuple(w * x for x in xs)),
        added=jax.jit(lambda a, b: a + b))


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` (both terms) and its gradient in
    every leaf of ``params`` (the program's tree), a part at a time:
    forward through the model and the MTP module keeping each part's
    input, then the two heads, then the module's parts, its glue and the
    model's parts from the last to the first, each recomputed under
    ``jax.vjp``. ``visit(where, grads)`` is handed each set of float32
    gradients as it is known (``where``: ``()`` for the top level's
    leaves, ``("mtp",)`` for the module's, else (stack, index) or
    ("mtp", stack, index)); nothing of them is kept here. -> (the loss,
    its main term, its MTP term unweighted, [tokens that chose each held
    expert, an expert layer of the model])."""
    read, run = _through(round_to), _reference_programs(c)
    tokens, targets = batch["tokens"], batch["targets"]
    every, has_next = _masks(targets)
    mp = params["mtp"]

    def layer(tree, spec):
        return _unstack(round_to)(tree[spec.stack], spec.index)

    def sweep(tree, plan, x, inputs, loads):
        no_dy = jnp.zeros_like(x)
        for spec in plan:
            inputs.append(x)
            x, load, _ = run.layer[spec.mixer](layer(tree, spec), x, no_dy)
            if spec.mixer is None:
                loads.append(load)
        return x

    def back(tree, plan, inputs, dx, where):
        for spec in reversed(plan):
            _, _, (d_lp, dx) = run.layer[spec.mixer](
                layer(tree, spec), inputs.pop(), dx)
            visit(where + (spec.stack, spec.index), d_lp)
            del d_lp
        return dx

    embed, head = read(params["embed"]), read(params["lm_head"])
    inputs, loads = [], []
    x = sweep(params, c.layer_plan(), run.embed(embed, tokens), inputs,
              loads)
    main, (d_norm, d_head, dx) = run.head(
        read(params["final_norm"]), head, x, targets, every)
    visit((), {"final_norm": d_norm})
    del d_norm
    # the MTP module, on the stream before the final norm
    gp = {name: read(mp[name]) for name in _GLUE}
    nxt = run.embed(embed, targets)
    m_inputs = []
    m0, _ = run.glue(gp, nxt, x, jnp.zeros_like(x))
    m = sweep(mp, c.layer_plan(mtp=True), m0, m_inputs, [])
    del m0
    mtp, (d_norm, d_head2, dm) = run.head(
        read(mp["final_norm"]), head, m, jnp.roll(targets, -1, 1), has_next)
    del m, head
    d_norm, d_head2, dm = run.scaled(c.mtp_weight, d_norm, d_head2, dm)
    visit((), {"lm_head": run.added(d_head, d_head2)})
    del d_head, d_head2
    visit(("mtp",), {"final_norm": d_norm})
    dm = back(mp, c.layer_plan(mtp=True), m_inputs, dm, ("mtp",))
    _, (d_gp, d_nxt, dx_mtp) = run.glue(gp, nxt, x, dm)
    del dm, nxt, x, gp
    visit(("mtp",), d_gp)
    del d_gp
    dx = back(params, c.layer_plan(), inputs, run.added(dx, dx_mtp), ())
    visit((), {"embed": run.d_embed(dx, tokens, d_nxt, targets)})
    return main + c.mtp_weight * mtp, main, mtp, loads


def _ssd_weighted(X, dt, A, Bm, Cm, D, w):
    out = ssd_recurrence(X, dt, A, Bm, Cm, D)
    return jnp.sum(out * w), out


@jax.jit
def reference_ssd(X, dt, A, Bm, Cm, D, w):
    """The recurrence token by token in float32 on the operands (any
    dtype, read as float32) and the gradients of ``sum(out * w)`` ->
    (out, dX, ddt, dA, dB, dC, dD), float32."""
    grads, out = jax.grad(_ssd_weighted, argnums=(0, 1, 2, 3, 4, 5),
                          has_aux=True)(
        *(x.astype(F32) for x in (X, dt, A, Bm, Cm, D, w)))
    return (out,) + grads


@functools.partial(jax.jit, static_argnames="chunk")
def _program_ssd(X, dt, A, Bm, Cm, D, w, chunk):
    from horovod_tpu.ops.ssd import ssd

    def f(X, dt, A, Bm, Cm, D, w):   # w rides as an argument
        out = ssd(X, dt, A, Bm, Cm, D, chunk)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    grads, out = jax.grad(f, argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(
        X, dt, A, Bm, Cm, D, w)
    return (out,) + grads


@functools.partial(jax.jit, static_argnames=("shape", "groups", "states"))
def _ssd_operands(key, shape, groups, states):
    """Operands as the mixer hands them to the recurrence: ``X`` after a
    SiLU and ``w`` [B, T, H, P] in bf16; ``dt`` = softplus of a normal
    round log-uniform starts over (1e-3, 0.1) a head, float32 (small and
    large steps side by side); ``A`` = -uniform(1, 16) a head; ``B`` and
    ``C`` [B, T, G, N] after a SiLU, scaled so that ``C . B`` is of
    order one, in bf16; ``D`` near 1."""
    ks = jax.random.split(key, 8)
    b, t, h, _ = shape
    start = jnp.exp(jax.random.uniform(ks[1], (h,), F32, np.log(1e-3),
                                       np.log(0.1)))
    bias = start + jnp.log(-jnp.expm1(-start))     # softplus's inverse

    def bc(k):
        return (jax.nn.silu(jax.random.normal(k, (b, t, groups, states),
                                              F32))
                * states ** -0.25).astype(jnp.bfloat16)

    return (jax.nn.silu(jax.random.normal(ks[0], shape, F32)
                        ).astype(jnp.bfloat16),
            jax.nn.softplus(bias + jax.random.normal(ks[2], (b, t, h), F32)),
            -jax.random.uniform(ks[3], (h,), F32, 1.0, 16.0),
            bc(ks[4]), bc(ks[5]),
            1.0 + 0.1 * jax.random.normal(ks[6], (h,), F32),
            jax.random.normal(ks[7], shape, jnp.bfloat16))


# ---------------------------------------------------------------------
# What a step REQUIRES, from shapes (beside ``ssd_counts.py``).
# ---------------------------------------------------------------------

def matmul_params_per_token(c, kinds, mtp_kinds, rows_share):
    """Parameters that multiply ONE token: a mamba2 layer's two
    projections, an attention layer's four, an expert layer's router,
    latent projections and shared expert and its HELD experts' two
    matrices at the share ``rows_share`` of a token's rows that reach
    them; ``eh_proj``; the head twice where the MTP module runs. Not the
    lookups (gathers), not the gains, taps, biases, ``A`` or ``D``."""
    d, l = c.d_model, c.moe_latent
    di, gn = c.ssd_d_inner, c.ssd_groups * c.ssd_state
    part = {
        "mamba2": d * (2 * di + 2 * gn + c.ssd_heads) + di * d,
        "attention": d * c.head_dim * (2 * c.n_heads + 2 * c.n_kv_heads),
        None: d * c.n_experts + 2 * d * l + 2 * d * c.shared_width
        + rows_share * 2 * l * c.expert_width}
    heads = 2 if mtp_kinds else 1
    return sum(part[k] for k in kinds + mtp_kinds) \
        + (2 * d * d if mtp_kinds else 0) + heads * d * c.vocab_size


# ---------------------------------------------------------------------

class Model(jamba.Model):
    """Kind "jamba"'s adapter (the step of a state that fills the chip;
    through it kind "afmoe"'s kept batch and flash comparison) with
    Nemotron-3-Super's configuration and share, its counts and its
    comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        assert config["mlp_hidden_act"] == "relu2" \
            and config["n_group"] == 1 and config["topk_group"] == 1 \
            and not config["tie_word_embeddings"] \
            and not (config["use_bias"] or config["mamba_proj_bias"]
                     or config["mlp_bias"] or config["attention_bias"]) \
            and config["mamba_num_heads"] * config["mamba_head_dim"] \
            == config["expand"] * config["hidden_size"] \
            and len(config["hybrid_override_pattern"]) \
            == config["num_hidden_layers"], config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            n_experts=config["reduced"]["n_routed_experts"]["published"],
            one_part_layers=True,
            layer_types=tuple(_PARTS[ch] for ch in
                              config["hybrid_override_pattern"]),
            mtp_types=tuple(_PARTS[ch] for ch in
                            config["mtp_hybrid_override_pattern"])
            if config["num_nextn_predict_layers"] else (),
            mtp_weight=a["mtp_weight"]
            if config["num_nextn_predict_layers"] else 0.0,
            ffn_act="relu2", score_func="sigmoid", moe_impl="grouped",
            moe_aux_weight=0.0, loss_chunk=a["loss_chunk"],
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        # rescale_prenorm_residual: every projection that writes into the
        # stream starts 1 / sqrt(the PUBLISHED depth) smaller
        self.out_scale = config["reduced"]["num_hidden_layers"][
            "published"] ** -0.5 if config["rescale_prenorm_residual"] \
            else 1.0
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on
        self.rows_held = None      # a layer, from the reference's router

    def init(self, key):
        params, state = super().init(key)
        scaled = ("ssd_out", "wo", "moe_down", "shared_down")

        def rescale(tree):
            return {k: rescale(v) if isinstance(v, dict)
                    else (v * self.out_scale).astype(v.dtype)
                    if k in scaled else v for k, v in tree.items()}

        return rescale(params), state

    # -- counts ---------------------------------------------------------

    def _kinds(self, mtp=False):
        return [spec.mixer for spec in self.cfg.layer_plan(mtp)]



    def _rows_share(self):
        """The share of a token's K rows that reach the held experts."""
        n = self._kinds().count(None)
        rows = self.rows_held or [self.even_share] * n
        return sum(rows) / len(rows) / self.units_per_step

    def ssd_work(self):
        """(required FLOPs, required bytes) of the SSD recurrences of a
        step: ``ssd_core_roofline_pct``'s numerator."""
        c = self.cfg
        shape = (self.units_per_step, c.ssd_heads, c.ssd_head_dim,
                 c.ssd_state, c.ssd_groups, self._kinds().count("mamba2"))
        return (ssd_counts.core_flops(*shape), ssd_counts.core_bytes(
            *shape, jnp.dtype(c.compute_dtype).itemsize))

    def flops_per_unit(self):
        c = self.cfg
        kinds = self._kinds() + self._kinds(mtp=True)
        params = matmul_params_per_token(
            c, self._kinds(), self._kinds(mtp=True), self._rows_share())
        attn = kinds.count("attention") * afmoe_counts.attention_flops(
            1, self.seq, c.n_heads, c.head_dim) / self.seq
        return 6 * params + attn + self.ssd_work()[0] / self.units_per_step

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """The grad program must never name an array of tokens x heads x
        channels x states (the recurrence materialised) and, on the chip,
        must hold the flash forward kernel, the SSD kernel pair and
        megablox's grouped GEMMs by name, not their reference
        branches."""
        c = self.cfg
        whole = self.units_per_step * c.ssd_d_inner * c.ssd_state
        if jamba.largest_tensor(text) >= whole:
            return "grad program names a tensor of " \
                   f"{jamba.largest_tensor(text)} elements: the " \
                   f"recurrence's states materialised ({whole})"
        if not on_tpu:
            return None
        missing = [name for name in (
            "tpu_custom_call", "hvd_flash_fwd", "hvd_ssd_fwd",
            "hvd_ssd_bwd", "@gmm", "@tgmm") if name not in text]
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
        got, params = self._step_readings(params, batch, say)

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            faults = self._check_flash(ks[0], say) \
                + self._check_ssd(ks[1], say)
            for name, (k, n) in (
                    ("up", (c.moe_latent, c.expert_width)),
                    ("down", (c.expert_width, c.moe_latent))):
                faults += check_grouped_mm(
                    jax.random.fold_in(ks[2], k), self.row_bound(),
                    self.even_share, k, n, c.n_experts_held, name, say,
                    self._grouped_mm)
            return faults + self._check_step(params, batch, got, say)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.


    def _ssd(self, *operands):
        """-> (out, dX, ddt, dA, dB, dC, dD) of ``sum(out * w)``."""
        return _program_ssd(*operands, chunk=self.cfg.ssd_chunk)

    def _check_ssd(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.ssd_heads, c.ssd_head_dim)
        operands = _ssd_operands(key, shape, c.ssd_groups, c.ssd_state)
        err = dict(zip(("fwd", "dX", "ddt", "dA", "dB", "dC", "dD"), map(
            float, _rel_errs(self._ssd(*operands),
                             reference_ssd(*operands)))))
        flops, nbytes = self.ssd_work()
        dev = jax.local_devices()[0]
        say(event="ssd_vs_token_by_token", shape=list(shape),
            states=c.ssd_state, groups=c.ssd_groups, err=err, tol=SSD_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=ssd_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"ssd {name} error {e} vs the recurrence token by token"
                for name, e in err.items() if not e <= SSD_TOL]

    def _check_step(self, params, batch, got, say):
        """``got`` (:meth:`_step_readings`) against the reference on the
        same weights and batch; also says ``expert_load``."""
        c = self.cfg
        err = {}
        lr, eps = self.opt["learning_rate"], self.opt.get("eps", 1e-8)
        # The step's gradients and the parameters after it wait on the
        # HOST while the reference runs (5.5 GB the reference's Mamba-2
        # layer needs: 4.8 GB of float32 residuals a layer), a layer's
        # leaves come back as they are compared.
        host = [jax.device_get(got.pop("grads")), jax.device_get(params),
                jax.device_get(got.pop("after"))]

        def visit(where, ref):
            def leaves(tree):
                for name in where[:-2] if len(where) > 1 else where:
                    tree = tree[name]
                if len(where) > 1:
                    return {name: tree[where[-2]][name][where[-1]]
                            for name in ref}
                return {name: tree[name] for name in ref}

            readings = jax.device_get(_leaves_readings(
                *(leaves(tree) for tree in host), ref, None, lr, eps))
            for name, e in readings.items():
                for reading, value in e.items():
                    key = f"{reading}_{'mtp.' if 'mtp' in where else ''}" \
                          f"{name}"
                    err[key] = max(err.get(key, 0.0), float(value))

        loss, main, mtp, loads = (
            float(x) if i < 3 else x for i, x in enumerate(
                reference_loss_and_grads(params, batch, c, visit)))
        err["loss"] = abs(float(got["loss"]) - loss) / abs(loss)
        # the program's second term, from its loss and the reference's
        # first: a dropped, mis-weighted or mis-shifted MTP term shows
        # here whole, where in the sum it is a tenth
        got_mtp = (float(got["loss"]) - main) / c.mtp_weight
        err["mtp_loss"] = abs(got_mtp - mtp) / abs(mtp)
        afmoe.Model._say_expert_load(self, np.asarray(loads), batch, say)
        say(event="step_vs_reference", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            tol={"loss": LOSS_TOL, "mtp_loss": MTP_LOSS_TOL, "d_": GRAD_TOL,
                 "d_ of " + ", ".join(ROUTED_LEAVES): ROUTED_GRAD_TOL,
                 "moved_": MOVED_TOL},
            loss=float(got["loss"]), reference_loss=loss,
            reference_main_term=main, reference_mtp_term=mtp)
        return [f"the step's {name} error {e} vs the float32 reference"
                for name, e in err.items() if not e <= _bound(name)]


def _bound(reading):
    """The bound of a reading of ``step_vs_reference``."""
    if reading in ("loss", "mtp_loss"):
        return LOSS_TOL if reading == "loss" else MTP_LOSS_TOL
    kind, leaf = reading.split("_", 1)
    if kind == "moved":
        return MOVED_TOL
    return ROUTED_GRAD_TOL if leaf.removeprefix("mtp.") in ROUTED_LEAVES \
        else GRAD_TOL


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the four
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _flash(self, q, k, v, w, window):
        return afmoe.reference_attention(_fp8(q), _fp8(k), _fp8(v), w,
                                         window)

    def _ssd(self, X, dt, A, Bm, Cm, D, w):
        return reference_ssd(_fp8(X), _fp8(dt), _fp8(A), _fp8(Bm),
                             _fp8(Cm), _fp8(D), w)

    def _grouped_mm(self, lhs, rhs, cot, sizes):
        return afmoe._fp8_grouped_mm(lhs, rhs, cot, sizes)

    def _step_readings(self, params, batch, say):
        """The reference's gradients wait on the host in the storage
        dtype (what the grad program hands back), a leaf a layer, and
        are stacked there. No step of the program follows the window
        here: the gradient buffers it left go."""
        from horovod_tpu.parallel import train_step

        train_step.drop_spare_gradients()
        seen = {}

        def keep(where, ref):
            seen.setdefault(where, {}).update(
                {name: np.asarray(g.astype(params["embed"].dtype))
                 for name, g in ref.items()})

        loss = reference_loss_and_grads(params, batch, self.cfg, keep,
                                        round_to=FP8)[0]

        def stacked(tree, lead):
            grads = seen.pop(lead)
            for stack in {w[-2] for w in seen if w[:-2] == lead
                          and len(w) == len(lead) + 2}:
                n = len([w for w in seen if w[:-1] == lead + (stack,)])
                grads[stack] = {name: np.stack(
                    [seen[lead + (stack, i)][name] for i in range(n)])
                    for name in tree[stack]}
            return grads

        grads = stacked(params, ())
        grads["mtp"] = stacked(params["mtp"], ("mtp",))
        grads = jax.device_put(grads)
        say(event="the_reference_in_fp8_in_the_programs_place")
        return {"loss": loss, "grads": grads,
                "after": jax.tree.map(
                    lambda p, g: adam_first_step(p, g.astype(F32),
                                                 self.opt), params,
                    grads)}, params


COMPARISONS = ("flash", "ssd", "grouped GEMM", "the step")


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
    _, _, config, traffic = child.find_cell("nemotron3super.spmd.b1s8192")
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
