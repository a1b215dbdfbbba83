"""Model adapter for kind "minicpmsala": MiniCPM-SALA's decoder (a layer
is a mixer and a SwiGLU FFN under MiniCPM's muP scalings; the mixer is
InfLLM-V2 block-sparse attention, 32 query heads on 2 key/value heads
each token attending at most 64 blocks of 64 keys a group, or Lightning
linear attention, 32 heads of a 128 x 128 state under a constant decay)
as ONE chip of the first of eight pipeline stages holds it: published
layers 0-3 whole, an eighth of the vocabulary. Run through the program's
own ``LlamaConfig`` / ``llama_init`` / ``llama_loss``, the path every LM
kind takes; this adapter extends kind "jamba"'s (the step of a state that
fills the chip) and through it kind "afmoe"'s (the batch it keeps, the
comparisons' glue). Nothing of the model is re-implemented here except
the plain float32 reference that ``correct`` is decided against: the
benchmark's own copy (the program keeps one in
``horovod_tpu/models/reference.py``, which a later PR may edit; this one
it may not).

What ``correct`` means for this kind, outside the window, at published
widths and at the TIMED sizes (bounds and the readings they were set
from: below, and PERF.md section 2):

(a) the program's SELECTION (``ops/sparse_attention.py:select_blocks``)
    at [batch, seq, 32 on 2, 128] against the reference's five steps in
    float32, a block of query rows at a time: the share of (token,
    group) pairs whose SET equals the reference's, and for every pair
    that differs the reference's score margin between the blocks swapped;
(b) the sparse core, forward and the gradients of ``q``, ``k``, ``v``,
    against explicit-mask float32 attention in blocks of query rows
    GIVEN THE PROGRAM'S selection (a near-tie that flipped is not
    charged, a block left out is);
(c) ``ops/ssd.py`` at [batch, seq, 32, 128] x 128 states, 32 groups, ``dt
    = 1``, no ``D``, against the recurrence TOKEN BY TOKEN in float32,
    forward and the gradients of ``q``, ``k``, ``v``;
(d) ONE MORE STEP OF THE TIMED PROGRAMS, on the batch the run trained on
    and the weights it ended with, against the reference a layer at a
    time and in blocks: the program's selection of THAT step (its own
    norm, projections and q/k norm on the weights it ended with) against
    the reference's five steps at the same input, by the verdict and the
    limits of (a); then, the sparse layer's reference given the
    program's sets, the loss, EVERY gradient leaf (l2), the norm of every
    leaf's change under the reference's own first Adam step.

Printed on earlier lines, not judged: the mean number of blocks a token
attends, the share of them forced, the mean blocks a tile visits over
the mean its rows chose.

The control (``python3 -m chipbench.models.minicpmsala --seed N``): the
same run with the REFERENCE computed in fp8 put in the program's place
in all four comparisons, through the same verdicts; it has to come out
not correct in each, and in (d) by each of the loss, a gradient leaf and
a leaf's change.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import sala_counts
from chipbench.models import jamba, lm
from chipbench.models.afmoe import (
    F32,
    FP8,
    _block,
    _fp8,
    _leaves_readings,
    _over_blocks,
    _rel_errs,
    _rms,
    _swiglu,
    _through,
    adam_first_step,
)

# published config.json key -> LlamaConfig field
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
         "intermediate_size": "d_ff", "rms_norm_eps": "norm_eps",
         "rope_theta": "rope_theta", "lightning_nh": "lightning_heads",
         "lightning_head_dim": "lightning_head_dim",
         "scale_emb": "embed_mult"}
# mixer_types' names -> ``layer_types``
_MIXERS = {"minicpm4": "sparse_attention",
           "lightning-attn": "lightning_attention"}

# The bounds, each with the two readings it stands between (TPU v5e, my
# chip runs, PR 55; PERF.md section 2): the largest the PROGRAM read
# over its seeds, and what the REFERENCE reads in the program's place
# with its operands (for the step, its matrices) rounded to fp8 (e4m3,
# the nearest precision below the configuration's bf16), which has to
# fail (``Fp8InTheProgramsPlace``).
# (a) The selection. The share of (token, group) pairs whose set is the
# reference's, and the largest margin, in the reference's own block
# scores, between a block the reference chose and the block the program
# chose in its place, as a share of the former. The program differs from
# the reference by the pooled keys' one rounding to bf16 (2^-9 of a
# score of order 0.2, so 1e-3 of a softmax entry): 0.9504-0.9526 of the
# sets equal, a margin of 3.5e-4 - 4.7e-4 at worst (512 blocks of which
# 31 are free choices: one set in twenty holds a near-tie). fp8: 0.383 of
# the sets, 1.06e-2. (A set of another SIZE is no near-tie: infinity.)
SELECT_SAME_MIN = 0.75
SELECT_MARGIN_TOL = 3e-3
# (b) The sparse core given the program's sets, max-abs error over the
# largest entry: forward, backward (bf16 operands, ``p`` and ``ds``
# rounded where they enter a matmul). Program, the largest of the four a seed, 0.0036-0.0046 over
# six seeds; fp8 0.062 / 0.037, 0.049, 0.0186.
SPARSE_TOL = {"fwd": 8e-3, "bwd": 8e-3}
# (c) The recurrence, the same statistic: out, dq, dk, dv (rounded to
# bf16 as they leave, the decayed scores, ``v`` and the state as they
# enter a matmul). Program 0.0038-0.0050; fp8 0.033-0.048.
LIGHTNING_TOL = 1.2e-2
# (d) The step. First the selection on the step's REAL activations: the
# program's table at each sparse layer's input (its own norm,
# projections, q/k norm and ``select_blocks`` in bf16) against the
# reference's five steps on its own float32 ``q`` and ``k`` there, under
# the limits of (a): the same verdict, and here the program differs by
# the projections' rounding to bf16 as well as the pooled keys'.
# The loss, relative: program 1.39e-5 - 1.49e-5 over twelve untraced
# seeds, 1.95e-5 in the traced run; fp8 7.7e-5 (four layers on seeded
# weights read ln(vocabulary) at any precision, 9.047 of ln 9181 =
# 9.125, so the two lie a factor of four apart and no more): the limit
# stands between them, twice the program's largest.
LOSS_TOL = 4e-5
# A gradient leaf's l2 error, the worst layer. Program: ``wk``, ``wq``,
# ``wv``, ``wo``, ``wg``, ``attn_norm`` 0.024-0.025, ``mlp_norm`` 0.021,
# the FFN's three, the q/k gains and ``out_norm`` 0.015-0.016, ``embed``
# 0.014, ``final_norm`` and ``lm_head`` 0.011; fp8 0.219-0.274 in
# fourteen leaves, ``lm_head`` 0.148 (``final_norm`` 0.043 passes: the
# kind is refused by the other fifteen).
GRAD_TOL = 0.08
# The norm of a leaf's change against that of the reference's own first
# Adam step, the worst leaf. Program 0.00045-0.00053 over thirteen seeds
# (``wq``, ``wg``, ``wo``); fp8 0.0031 (``wg``), 0.0016-0.0023 in six
# more matrices: the limit stands between them, 2.5 times the program's
# largest (an unchanged state reads 1, three hundred times the fp8
# reading: no limit near it could refuse the lower precision).
MOVED_TOL = 1.3e-3
TOKEN_BLOCK = 2048
# Query rows a block of the reference's attention and selection.
ROW_BLOCK = 128
# Tokens between two states the reference's recurrence keeps for its
# backward pass (``jax.checkpoint`` a segment): memory, not mathematics.
SEGMENT = 64


# ---------------------------------------------------------------------
# The plain reference: float32 jax.numpy under "highest" matmul
# precision, a Python loop over layers, the lightning recurrence TOKEN BY
# TOKEN as it is written (a ``lax.scan`` over tokens: no chunk, no
# kernel), the sparse layer's attention under an explicit mask built
# from the chosen blocks, the selection's five steps written out from
# the published description; nothing imported from the program but the
# rule that says in which stack a layer's parameters lie
# (``LlamaConfig.layer_plan``). The equations and the departures:
# horovod_tpu/models/reference.py. So that it fits at the cell's 32,768
# tokens the SAME math runs in blocks (query rows, token blocks), and the
# gradients a layer at a time. One block is the whole.
# ---------------------------------------------------------------------

def rates_of(c, layer):
    """``-s_n f_l`` [H] of the published layer ``layer``."""
    H, L = c.lightning_heads, c.lightning_depth or c.n_layers
    return -(2.0 ** (-8.0 * (jnp.arange(H, dtype=F32) + 1.0) / H)) \
        * (1.0 - layer / (L - 1) + 1e-5)


def lightning_recurrence(q, k, v, rates):
    """``S_t = exp(rate) S_{t-1} + k_t v_t^T; o_t = S_t^T q_t`` from
    ``S_0 = 0``, token by token: ``q``, ``k``, ``v`` [B, T, H, d]
    float32, ``rates`` [H] -> [B, T, H, d]."""
    b, t, h, d = q.shape
    forget = jnp.exp(rates)[:, None, None]

    def token(S, x):
        qt, kt, vt = x                                        # [B, H, d]
        S = forget * S + kt[..., :, None] * vt[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    seg = _block(t, SEGMENT)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, b, h, d)
               for x in (q, k, v))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(token, S, x)),
        jnp.zeros((b, h, d, d), F32), xs)
    return jnp.moveaxis(o.reshape(t, b, h, d), 0, 1)


def _rope(x, theta):
    """Half-split rotation of ``x`` [B, T, H, d] by its position."""
    t, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def lightning_mixer(h, lp, c, rates):
    b, t, _ = h.shape
    H, d = c.lightning_heads, c.lightning_head_dim

    def heads(w):
        return (h @ lp[w]).reshape(b, t, H, d)

    q = _rope(_rms(heads("wq"), lp["q_norm"], c.norm_eps), c.rope_theta)
    k = _rope(_rms(heads("wk"), lp["k_norm"], c.norm_eps), c.rope_theta)
    o = lightning_recurrence(q, k, heads("wv"), rates) / d ** 0.5
    y = _rms(o.reshape(b, t, H * d), lp["out_norm"], c.norm_eps)
    return (y * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]


def _sizes(c):
    return types.SimpleNamespace(
        block=c.sparse_block, topk=c.sparse_topk, kernel=c.sparse_kernel,
        stride=c.sparse_stride, init=c.sparse_init_blocks,
        window=c.sparse_window_blocks)


def pooled_keys(k, z):
    """Step 1: ``kbar_j = mean(k[stride j : stride j + kernel])``, ``k``
    [B, T, G, d] float32 -> [B, J, G, d]."""
    t = k.shape[1]
    nj = (t - z.kernel) // z.stride + 1
    at = z.stride * jnp.arange(nj)[:, None] + jnp.arange(z.kernel)[None, :]
    return jnp.mean(k[:, at], 2)                  # [B, J, kernel, G, d]


def selection_rows(q, kbar, t0, z, n_blocks):
    """Steps 2-5 for the query rows ``q`` [B, R, G, n, d] at positions
    ``t0 ..`` -> (the sets, bool [B, R, G, nb]; the blocks' scores of
    step 4, -inf where no window is admitted, [B, R, G, nb])."""
    R, d = q.shape[1], q.shape[-1]
    nj, r = kbar.shape[1], z.block // z.stride
    t = t0 + jnp.arange(R)
    admitted = (z.stride * jnp.arange(nj) + z.kernel - 1)[None, :] \
        <= t[:, None]                                        # [R, J]
    adm = admitted[:, None, None, :]
    s = jnp.einsum("brgnd,bjgd->brgnj", q, kbar) / d ** 0.5
    lse = jax.nn.logsumexp(jnp.where(adm, s, -1e30), -1, keepdims=True)
    p = jnp.where(adm, jnp.exp(jnp.where(adm, s, 0.0) - lse), 0.0)
    P = jnp.where(admitted[:, None, :], jnp.sum(p, 3), -jnp.inf)
    # window j of block b: j in [r b - 1, r b + r - 1], those that exist
    j = r * jnp.arange(n_blocks)[:, None] + jnp.arange(-1, r)[None, :]
    exists = (j >= 0) & (j < nj)
    score = jnp.max(jnp.where(exists, P[..., jnp.clip(j, 0, nj - 1)],
                              -jnp.inf), -1)                 # [B, R, G, nb]
    blk = jnp.arange(n_blocks)
    own = (t // z.block)[:, None]
    begun = (blk <= own)[None, :, None, :]
    forced = begun & ((blk < z.init) | (blk > own - z.window)
                      )[None, :, None, :]
    sel = jnp.broadcast_to(forced, score.shape)

    def one_more(_, sel):
        room = jnp.sum(sel, -1, keepdims=True) < z.topk
        left = jnp.where(begun & ~sel, score, -jnp.inf)
        best = jax.nn.one_hot(jnp.argmax(left, -1), n_blocks, dtype=bool)
        return sel | (best & room & (jnp.max(left, -1, keepdims=True)
                                     > -jnp.inf))

    return jax.lax.fori_loop(0, z.topk, one_more, sel), score


def attend_rows(q, k, v, sel, t0, block):
    """Explicit-mask attention of the query rows ``q`` [B, R, G, n, d] at
    positions ``t0 ..`` over ``k``, ``v`` [B, T, G, d] given the rows'
    sets ``sel`` [B, R, G, nb] (None: every earlier key)."""
    R, T, d = q.shape[1], k.shape[1], q.shape[-1]
    t = t0 + jnp.arange(R)
    mask = (jnp.arange(T)[None, :] <= t[:, None])[None, :, None, :]
    if sel is not None:
        mask = mask & jnp.repeat(sel, block, -1)
    s = jnp.einsum("brgnd,bsgd->brgns", q, k) / d ** 0.5
    p = jax.nn.softmax(jnp.where(mask[:, :, :, None, :], s, -jnp.inf), -1)
    return jnp.einsum("brgns,bsgd->brgnd", p, v)


def _by_rows(f, xs, t):
    """``f(*blocks of xs, first position)`` over blocks of ``ROW_BLOCK``
    query rows (axis 1 of each array of the tuple ``xs``), recomputed in the
    backward pass -> the results' blocks joined along axis 1."""
    rows = _block(t, ROW_BLOCK)

    def lead(a):
        return jnp.moveaxis(a.reshape(a.shape[0], t // rows, rows,
                                      *a.shape[2:]), 1, 0)

    def join(a):
        return jnp.moveaxis(a, 0, 1).reshape(a.shape[1], t, *a.shape[3:])

    out = jax.lax.map(lambda x: jax.checkpoint(f)(*x[0], x[1]),
                      (tuple(lead(a) for a in xs),
                       jnp.arange(t // rows) * rows))
    return jax.tree.map(join, out)


def reference_selection(q, k, c):
    """``q`` [B, T, H, d], ``k`` [B, T, G, d] float32 -> (the sets, the
    blocks' scores), [B, T, G, nb] each."""
    b, t, h, d = q.shape
    g = k.shape[2]
    z = _sizes(c)
    kbar = pooled_keys(k, z)
    return _by_rows(
        lambda q, t0: selection_rows(q, kbar, t0, z, t // z.block),
        (q.reshape(b, t, g, h // g, d),), t)


def sparse_attention(q, k, v, sel, block):
    """``q`` [B, T, H, d], ``k``, ``v`` [B, T, G, d] float32, ``sel``
    bool [B, T, G, nb] -> [B, T, H, d]."""
    b, t, h, d = q.shape
    g = k.shape[2]
    return _by_rows(
        lambda q, sel, t0: attend_rows(q, k, v, sel, t0, block),
        (q.reshape(b, t, g, h // g, d), sel), t).reshape(b, t, h, d)


def _sparse_qk(h, lp, c):
    """``q`` [B, T, H, d] and ``k`` [B, T, G, d] of a sparse layer: the
    projections of ``h`` under the q/k norm a head, no position."""
    b, t, _ = h.shape
    hd = c.head_dim
    return (_rms((h @ lp["wq"]).reshape(b, t, c.n_heads, hd), lp["q_norm"],
                 c.norm_eps),
            _rms((h @ lp["wk"]).reshape(b, t, c.n_kv_heads, hd),
                 lp["k_norm"], c.norm_eps))


def sparse_mixer(h, lp, c, sel):
    b, t, _ = h.shape
    q, k = _sparse_qk(h, lp, c)
    v = (h @ lp["wv"]).reshape(b, t, c.n_kv_heads, c.head_dim)
    a = sparse_attention(q, k, v, sel, c.sparse_block).reshape(b, t, -1)
    return (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]


def reference_layer_selection(lp, x, c):
    """The reference's OWN five steps at a sparse layer's input ``x``
    [B, T, D] with its float32 parameters ``lp`` -> (the sets, the
    blocks' scores), [B, T, G, nb] each."""
    with jax.default_matmul_precision("highest"):
        return reference_selection(
            *_sparse_qk(_rms(x, lp["attn_norm"], c.norm_eps), lp, c), c)


def reference_layer(lp, x, c, mixer, given):
    """One layer on ``x`` [B, T, D] with its float32 parameters ``lp``;
    ``given``: a lightning layer's rates [H], a sparse layer's sets [B,
    T, G, nb]."""
    b, t, d = x.shape
    r = c.residual_mult
    with jax.default_matmul_precision("highest"):
        h = _rms(x, lp["attn_norm"], c.norm_eps)
        if mixer == "lightning":
            y = lightning_mixer(h, lp, c, given)
        else:
            y = sparse_mixer(h, lp, c, given)
        x = x + r * y
        h = _rms(x, lp["mlp_norm"], c.norm_eps).reshape(b * t, d)
        ff = _over_blocks(
            lambda h, lp: _swiglu(h, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]),
            h, _block(b * t, TOKEN_BLOCK), lp)
        return x + r * ff.reshape(b, t, d)


def _head_loss(final_norm, lm_head, x, targets, c):
    """Mean cross-entropy over the vocabulary rows held, of ``x``
    [B, T, D] against ``targets`` [B, T], in blocks of tokens."""
    n = targets.size

    def nll(xt, final_norm, lm_head):
        x, target = xt
        logp = jax.nn.log_softmax(
            (_rms(x, final_norm, c.norm_eps) / c.logit_div) @ lm_head, -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_over_blocks(
            nll, (x.reshape(n, -1), targets.reshape(n)),
            _block(n, TOKEN_BLOCK), final_norm, lm_head))


@functools.lru_cache(maxsize=None)
def _reference_programs(c):
    """The reference's jitted programs for configuration ``c``, compiled
    once a process: ONE program a kind of mixer whatever the depth: the
    layer and its VJP under ``dy``. The forward sweep runs it too, with
    a zero ``dy`` and its gradients dropped (kind "afmoe" says why)."""
    def layer(mixer):
        def run(lp, x, dy, given):
            y, vjp = jax.vjp(
                lambda lp, x: reference_layer(lp, x, c, mixer, given), lp, x)
            return y, vjp(dy)
        return jax.jit(run)

    return types.SimpleNamespace(
        layer={mixer: layer(mixer)
               for mixer in {spec.mixer for spec in c.layer_plan()}},
        select=jax.jit(lambda lp, x: reference_layer_selection(lp, x, c)),
        embed=jax.jit(lambda e, t: c.embed_mult * e[t]),
        head=jax.jit(jax.value_and_grad(
            lambda g, w, x, t: _head_loss(g, w, x, t, c),
            argnums=(0, 1, 2))),
        d_embed=jax.jit(lambda dx, t: jnp.zeros(
            (c.vocab_size, c.d_model), F32).at[t].add(c.embed_mult * dx)))


@functools.lru_cache(maxsize=None)
def _read_layer(round_to):
    """One layer's stored leaves -> as :func:`afmoe._through` reads
    them; one program a kind of layer."""
    read = _through(round_to)
    return jax.jit(lambda lp: jax.tree.map(read, lp))


@functools.lru_cache(maxsize=None)
def _program_table(c):
    """The PROGRAM's selection at a sparse layer's input: its own norm,
    projections, q/k norm and ``select_blocks`` on the stream ``x`` in
    the compute dtype and the layer's leaves as stored -> the sets."""
    from horovod_tpu.models import llama
    from horovod_tpu.ops import sparse_attention as sa

    def table(lp, x):
        dt = c.compute_dtype
        h = llama._rmsnorm(x.astype(dt), lp["attn_norm"].astype(dt),
                           c.norm_eps)
        q, k, _ = llama._project_qkv(h, lp, c)
        return sa.chosen(sa.select_blocks(
            q, k, block=c.sparse_block, topk=c.sparse_topk,
            kernel=c.sparse_kernel, stride=c.sparse_stride,
            init_blocks=c.sparse_init_blocks,
            window_blocks=c.sparse_window_blocks))
    return jax.jit(table)


def reference_loss_and_grads(params, batch, c, visit, round_to=None):
    """The reference's loss on ``batch`` and its gradient in every leaf
    of ``params`` (the program's tree), a layer at a time: forward
    keeping each layer's input (and a sparse layer's sets: the PROGRAM's
    selection at that input, so that a near-tie that flipped is not
    charged to the step; what it is held to is the reference's OWN five
    steps at the same input, :func:`_selection_verdict`), then the head,
    then the layers from the last to the first, each recomputed under
    ``jax.vjp``. ``visit(where, grads)`` is handed each set of float32
    gradients as it is known (``where``: ``()`` for the top level's
    leaves, else (stack, index)); nothing of them is kept here. -> (the
    loss, each sparse layer's verdict: the share of sets equal, the
    worst margin of a swapped block, the mean blocks a token)."""
    read, run = _through(round_to), _reference_programs(c)
    tokens = batch["tokens"]
    plan = c.layer_plan()

    def stored(spec):
        """Layer ``spec``'s leaves as the program stores them, on the
        device: ``params`` may wait on the host (a tree of numpy
        arrays), so that one layer of it is here at a time."""
        return {name: jnp.asarray(w[spec.index])
                for name, w in params[spec.stack].items()}

    def layer(spec):
        return _read_layer(round_to)(stored(spec))

    x = run.embed(read(jnp.asarray(params["embed"])), tokens)
    inputs, given, verdicts, no_dy = [], [], [], jnp.zeros_like(x)
    for l, spec in enumerate(plan):
        inputs.append(x)
        if spec.mixer == "lightning":
            given.append(rates_of(c, l))
        else:
            given.append(_program_table(c)(stored(spec), x))
            verdicts.append(tuple(map(float, _selection_verdict(
                given[-1], *run.select(layer(spec), x)))))
        x, _ = run.layer[spec.mixer](layer(spec), x, no_dy, given[-1])
    del no_dy
    loss, (d_norm, d_head, dx) = run.head(
        read(jnp.asarray(params["final_norm"])),
        read(jnp.asarray(params["lm_head"])), x, batch["targets"])
    del x
    visit((), {"final_norm": d_norm, "lm_head": d_head})
    del d_norm, d_head
    for spec, sets in zip(reversed(plan), reversed(given)):
        _, (d_lp, dx) = run.layer[spec.mixer](layer(spec), inputs.pop(),
                                              dx, sets)
        visit((spec.stack, spec.index), d_lp)
        del d_lp
    visit((), {"embed": run.d_embed(dx, tokens)})
    return loss, verdicts


# ---------------------------------------------------------------------
# The kernels' comparisons: operands, the program's side, the reference's.
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "groups"))
def _attention_operands(key, shape, groups):
    """``q`` [B, T, H, d] and ``k`` [B, T, G, d] as the q/k norm leaves
    them (unit RMS a head), ``v`` and the cotangent weights ``w``
    standard normal, bf16."""
    b, t, h, d = shape
    ks = jax.random.split(key, 4)

    def unit(k, heads):
        x = jax.random.normal(k, (b, t, heads, d), F32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
                ).astype(jnp.bfloat16)

    return (unit(ks[0], h), unit(ks[1], groups),
            jax.random.normal(ks[2], (b, t, groups, d), jnp.bfloat16),
            jax.random.normal(ks[3], shape, jnp.bfloat16))


@functools.lru_cache(maxsize=None)
def _program_select(c):
    from horovod_tpu.ops import sparse_attention as sa

    return jax.jit(lambda q, k: sa.chosen(sa.select_blocks(
        q, k, block=c.sparse_block, topk=c.sparse_topk,
        kernel=c.sparse_kernel, stride=c.sparse_stride,
        init_blocks=c.sparse_init_blocks,
        window_blocks=c.sparse_window_blocks)))


@functools.lru_cache(maxsize=None)
def _reference_select(c):
    """-> jitted (q, k) -> (the reference's sets, its blocks' scores),
    under "highest" matmul precision."""
    def run(q, k):
        with jax.default_matmul_precision("highest"):
            return reference_selection(q.astype(F32), k.astype(F32), c)
    return jax.jit(run)


@jax.jit
def _selection_verdict(got, sets, score):
    """The program's sets ``got`` against the reference's ``sets`` and
    block scores ``score`` [B, T, G, nb] -> (the share of (token, group)
    pairs whose sets are equal; the largest margin over the pairs that
    differ: the best score among the blocks only the reference chose less
    the worst among those only the program chose, as a share of the
    former; mean blocks a token attends; the share of them forced is the
    caller's)."""
    ref_only, got_only = sets & ~got, got & ~sets
    same = ~jnp.any(ref_only | got_only, -1)
    best = jnp.max(jnp.where(ref_only, score, -jnp.inf), -1)
    worst = jnp.min(jnp.where(got_only, score, jnp.inf), -1)
    margin = jnp.where(same, 0.0, (best - worst) / jnp.maximum(best, 1e-30))
    # a program that chose another NUMBER of blocks is not a near-tie
    counts = jnp.sum(got, -1) == jnp.sum(sets, -1)
    margin = jnp.where(counts, margin, jnp.inf)
    return jnp.mean(same.astype(F32)), jnp.max(margin), \
        jnp.mean(jnp.sum(got, -1).astype(F32))


@functools.partial(jax.jit, static_argnames="block")
def _program_sparse(q, k, v, w, sets, block):
    from horovod_tpu.ops import sparse_attention as sa

    table = sa._pack(sets)

    def f(q, k, v, w):   # w rides as an argument, never closed over
        out = sa.sparse_attention(q, k, v, table, block)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    got, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v, w)
    return (out,) + got


@functools.partial(jax.jit, static_argnames="block")
def reference_sparse(q, k, v, w, sets, block):
    """Explicit-mask float32 attention given the sets and the gradients
    of ``sum(out * w)`` -> (out, dq, dk, dv), float32."""
    def f(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            out = sparse_attention(q, k, v, sets, block)
        return jnp.sum(out * w), out

    grads, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(
        *(x.astype(F32) for x in (q, k, v, w)))
    return (out,) + grads


@functools.partial(jax.jit, static_argnames="chunk")
def _program_lightning(q, k, v, w, rates, chunk):
    from horovod_tpu.ops.ssd import ssd

    def f(q, k, v, w):
        out = ssd(v, jnp.ones(v.shape[:3], F32), rates, k, q, None, chunk)
        return jnp.sum(out.astype(F32) * w.astype(F32)), out

    got, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v, w)
    return (out,) + got


@jax.jit
def reference_lightning(q, k, v, w, rates):
    """The recurrence token by token in float32 on the operands (any
    dtype, read as float32) and the gradients of ``sum(out * w)`` ->
    (out, dq, dk, dv), float32."""
    def f(q, k, v, w):
        out = lightning_recurrence(q, k, v, rates)
        return jnp.sum(out * w), out

    grads, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(
        *(x.astype(F32) for x in (q, k, v, w)))
    return (out,) + grads


# ---------------------------------------------------------------------

class Model(jamba.Model):
    """Kind "jamba"'s adapter (the step of a state that fills the chip;
    through it kind "afmoe"'s kept batch) with MiniCPM-SALA's
    configuration and share, its counts and its comparisons."""

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a, s = config["assumed"], config["assumed"]["sparse"]
        published = config["reduced"]["num_hidden_layers"]["published"]
        assert config["hidden_act"] == "silu" and config["qk_norm"] \
            and not config["attn_use_rope"] and config["lightning_use_rope"] \
            and config["attn_use_output_gate"] and config["use_output_gate"] \
            and config["use_output_norm"] \
            and config["lightning_scale"] == "1/sqrt(d)" \
            and config["lightning_nkv"] == config["lightning_nh"] \
            and not (config["attention_bias"]
                     or config["tie_word_embeddings"]) \
            and len(config["mixer_types"]) == config["num_hidden_layers"] \
            and s["kernel_size"] % s["kernel_stride"] == 0, config
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            layer_types=tuple(_MIXERS[m] for m in config["mixer_types"]),
            qk_norm="head", attn_gate=True,
            residual_mult=config["scale_depth"] / published ** 0.5,
            logit_div=config["hidden_size"] / config["dim_model_base"],
            lightning_depth=published,
            lightning_chunk=a["lightning_chunk"],
            sparse_block=s["block_size"], sparse_topk=s["topk"],
            sparse_kernel=s["kernel_size"], sparse_stride=s["kernel_stride"],
            sparse_init_blocks=s["init_blocks"],
            sparse_window_blocks=s["window_size"] // s["block_size"],
            sparse_dense_len=s["dense_len"],
            ffn_chunk=a["ffn_chunk"], loss_chunk=a["loss_chunk"],
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False
        self.trained_on = None     # the tokens the lane trains on

    # -- counts ---------------------------------------------------------

    def sparse_work(self):
        """(required FLOPs, required bytes) of the sparse cores of a
        step: ``sparse_core_roofline_pct``'s numerator."""
        c, layers = self.cfg, self._mixers().count("sparse")
        return (sala_counts.sparse_core_flops(
            self.batch_size, self.seq, c.n_heads, c.head_dim,
            c.sparse_block, c.sparse_topk, layers),
            sala_counts.sparse_core_bytes(
                self.batch_size, self.seq, c.n_heads, c.n_kv_heads,
                c.head_dim, layers, jnp.dtype(c.compute_dtype).itemsize))

    def lightning_work(self):
        """The same of the lightning recurrences:
        ``lightning_core_roofline_pct``'s numerator."""
        c, layers = self.cfg, self._mixers().count("lightning")
        shape = (self.units_per_step, c.lightning_heads,
                 c.lightning_head_dim, layers)
        return (sala_counts.lightning_core_flops(*shape),
                sala_counts.lightning_core_bytes(
                    *shape, jnp.dtype(c.compute_dtype).itemsize))

    def matmul_params_per_token(self):
        """Parameters that multiply ONE token: a sparse layer's five
        projections (``wk``, ``wv`` two heads wide), a lightning layer's
        five, each layer's SwiGLU, the head. Not the lookup, not the
        gains."""
        c, mixers = self.cfg, self._mixers()
        d, hd = c.d_model, c.head_dim
        hw = c.lightning_heads * c.lightning_head_dim
        return mixers.count("sparse") * d * hd * (
            3 * c.n_heads + 2 * c.n_kv_heads) \
            + mixers.count("lightning") * 5 * d * hw \
            + len(mixers) * 3 * d * c.d_ff + d * c.vocab_size

    def flops_per_unit(self):
        return 6 * self.matmul_params_per_token() \
            + (self.sparse_work()[0] + self.lightning_work()[0]) \
            / self.units_per_step

    # -- checks ---------------------------------------------------------

    def check_lowering(self, text, on_tpu):
        """On the chip the grad program must never name an array of
        tokens x tokens (a dense score plane) or of tokens x heads x a
        state (the recurrence materialised), and must hold the sparse
        pair and the SSD pair by name, not their reference branches (off
        it the explicit-mask form runs, whose plane a short sequence's
        one block of rows is)."""
        if not on_tpu:
            return None
        c = self.cfg
        whole = min(self.units_per_step * self.seq,
                    self.units_per_step * c.lightning_heads
                    * c.lightning_head_dim ** 2)
        if jamba.largest_tensor(text) >= whole:
            return "grad program names a tensor of " \
                   f"{jamba.largest_tensor(text)} elements: a score " \
                   f"plane or the recurrence's states materialised ({whole})"
        missing = [name for name in (
            "tpu_custom_call", "hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd",
            "hvd_ssd_fwd", "hvd_ssd_bwd") if name not in text]
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

        dev = jax.local_devices()[0]

        def say(**fields):   # how long the checks take, and what they hold
            heard(seconds_into_checks=round(time.time() - began, 1),
                  device_gb_in_use=round((dev.memory_stats() or {}).get(
                      "bytes_in_use", 0) / 1e9, 2), **fields)

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
            # The step's gradients, the parameters it started from and
            # those after it wait on the HOST from here on: the
            # reference's layer program takes 7.6 GB of the chip beside
            # one layer's float32 leaves, and is handed a layer at a time.
            host = {"grads": jax.device_get(got.pop("grads")),
                    "after": jax.device_get(got.pop("after")),
                    "params": jax.device_get(params)}
            del params
            return (self._check_sparse(ks[0], say)
                    + self._check_lightning(ks[1], say)
                    + self._check_step(host, batch, got, say))
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # What is compared with the reference: the program's. The control
    # (``Fp8InTheProgramsPlace``) puts the reference in fp8 here.

    def _select(self, q, k):
        """-> the sets, bool [B, T, G, nb]."""
        return _program_select(self.cfg)(q, k)

    def _sparse(self, q, k, v, w, sets):
        """-> (out, dq, dk, dv) of ``sum(out * w)``."""
        return _program_sparse(q, k, v, w, sets, self.cfg.sparse_block)

    def _lightning(self, q, k, v, w, rates):
        return _program_lightning(q, k, v, w, rates,
                                  self.cfg.lightning_chunk)

    def _check_sparse(self, key, say):
        """(a) and (b), on one draw of operands."""
        from horovod_tpu.ops import sparse_attention as sa

        c = self.cfg
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        q, k, v, w = _attention_operands(key, shape, c.n_kv_heads)
        got = self._select(q, k)
        sets, score = _reference_select(c)(q, k)
        same, margin, blocks = map(float, _selection_verdict(got, sets,
                                                             score))
        del sets, score
        t = np.arange(self.seq)
        begun = t // c.sparse_block + 1
        forced = np.minimum(begun, c.sparse_window_blocks) \
            + (begun > c.sparse_window_blocks) * c.sparse_init_blocks
        table = np.asarray(jax.jit(sa._pack)(got))
        visited = float((table != 0).sum(-1).mean())
        say(event="selection_vs_reference", shape=list(shape),
            kv_heads=c.n_kv_heads, sets_equal_share=same,
            worst_margin_of_a_swapped_block=margin,
            tol={"sets_equal_share_min": SELECT_SAME_MIN,
                 "margin": SELECT_MARGIN_TOL},
            blocks_a_token_mean=blocks,
            forced_share=float(forced.mean()) / blocks,
            blocks_a_tile_visits_mean=visited,
            visited_over_chosen=visited / blocks)
        faults = _selection_faults("selection", same, margin)
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._sparse(q, k, v, w, got),
            reference_sparse(q, k, v, w, got, c.sparse_block)))))
        flops, nbytes = self.sparse_work()
        dev = jax.local_devices()[0]
        say(event="sparse_core_vs_explicit_mask", shape=list(shape),
            given="the program's selection", err=err, tol=SPARSE_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=sala_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return faults + [
            f"sparse core {name} error {e} vs the explicit mask"
            for name, e in err.items()
            if not e <= SPARSE_TOL["fwd" if name == "fwd" else "bwd"]]

    def _check_lightning(self, key, say):
        c = self.cfg
        shape = (self.batch_size, self.seq, c.lightning_heads,
                 c.lightning_head_dim)
        q, k, v, w = _attention_operands(key, shape, c.lightning_heads)
        q = (q.astype(F32) * c.lightning_head_dim ** -0.5).astype(q.dtype)
        layer = self._mixers().index("lightning")
        rates = rates_of(c, layer)
        err = dict(zip(("fwd", "dq", "dk", "dv"), map(float, _rel_errs(
            self._lightning(q, k, v, w, rates),
            reference_lightning(q, k, v, w, rates)))))
        flops, nbytes = self.lightning_work()
        dev = jax.local_devices()[0]
        say(event="lightning_vs_token_by_token", shape=list(shape),
            rates_of_layer=layer, err=err, tol=LIGHTNING_TOL,
            required_flops_per_step=flops, required_bytes_per_step=nbytes,
            floor_ms=sala_counts.floor_s(dev.device_kind, flops, nbytes)
            * 1e3 if dev.platform == "tpu" else None)
        return [f"lightning {name} error {e} vs the recurrence token by "
                "token" for name, e in err.items()
                if not e <= LIGHTNING_TOL]

    def _check_step(self, host, batch, got, say):
        """``got`` (the step's loss) and ``host`` (its gradients, the
        parameters it started from and those after it, on the host)
        against the reference on the same weights and batch."""
        c = self.cfg
        err = {}
        lr, eps = self.opt["learning_rate"], self.opt.get("eps", 1e-8)
        trees = [host["grads"], host["params"], host["after"]]

        def visit(where, ref):
            def leaves(tree):
                if where:
                    return {name: tree[where[0]][name][where[1]]
                            for name in ref}
                return {name: tree[name] for name in ref}

            readings = jax.device_get(_leaves_readings(
                *(leaves(tree) for tree in trees), ref, None, lr, eps))
            for name, e in readings.items():
                for reading, value in e.items():
                    key = f"{reading}_{name}"
                    err[key] = max(err.get(key, 0.0), float(value))

        loss, verdicts = reference_loss_and_grads(host["params"], batch, c,
                                                  visit)
        loss = float(loss)
        err["loss"] = abs(float(got["loss"]) - loss) / abs(loss)
        say(event="step_vs_reference", tokens=int(batch["tokens"].size),
            on="the batch trained on" if self.trained_on is not None
            else "a seeded batch", err=err,
            tol={"loss": LOSS_TOL, "d_": GRAD_TOL, "moved_": MOVED_TOL,
                 "sets_equal_share_min": SELECT_SAME_MIN,
                 "margin": SELECT_MARGIN_TOL},
            loss=float(got["loss"]), reference_loss=loss,
            selection_of_the_step=[
                {"sets_equal_share": same,
                 "worst_margin_of_a_swapped_block": margin,
                 "blocks_a_token_mean": blocks}
                for same, margin, blocks in verdicts])
        return [fault for same, margin, _ in verdicts
                for fault in _selection_faults("the step's selection",
                                               same, margin)] \
            + [f"the step's {name} error {e} vs the float32 reference"
               for name, e in err.items() if not e <= _bound(name)]


def _selection_faults(what, same, margin):
    """The verdict of (a), on its operands and on the step's."""
    faults = []
    if not same >= SELECT_SAME_MIN:
        faults.append(f"{what}: only {same} of the sets are the "
                      "reference's")
    if not margin <= SELECT_MARGIN_TOL:
        faults.append(f"{what}: a block was swapped across a margin of "
                      f"{margin} of the reference's score")
    return faults


def _bound(reading):
    """The bound of a reading of ``step_vs_reference``."""
    if reading == "loss":
        return LOSS_TOL
    return MOVED_TOL if reading.startswith("moved_") else GRAD_TOL


# ---------------------------------------------------------------------
# The control: the reference, computed in fp8, in the program's place.
# ---------------------------------------------------------------------

class Fp8InTheProgramsPlace(Model):
    """The same run (the program trains as ever), but what the four
    comparisons read in the program's place is the float32 REFERENCE
    with its matrices and operands rounded to fp8 (e4m3), through the
    same verdicts. Every bound has to refuse it."""

    def _select(self, q, k):
        return _reference_select(self.cfg)(_fp8(q), _fp8(k))[0]

    def _sparse(self, q, k, v, w, sets):
        return reference_sparse(_fp8(q), _fp8(k), _fp8(v), w, sets,
                                self.cfg.sparse_block)

    def _lightning(self, q, k, v, w, rates):
        return reference_lightning(_fp8(q), _fp8(k), _fp8(v), w, rates)

    def _step_readings(self, params, batch, say):
        """The reference's gradients wait on the host in the storage
        dtype (what the grad program hands back), a leaf a layer, and
        are stacked there. No step of the program follows the window
        here: the gradient buffers it left go."""
        import gc

        from horovod_tpu.parallel import train_step

        # Nothing donates the parameters on this path: they go to the
        # host and their device buffers with them (the reference's layer
        # program takes 7.6 GB beside one layer's leaves), and what the
        # window left unreferenced goes now, not at the collector's time.
        train_step.drop_spare_gradients()
        kept = jax.device_get(params)
        jax.tree.map(lambda x: x.delete(), params)
        gc.collect()
        seen = {}

        def keep(where, ref):
            seen.setdefault(where, {}).update(
                {name: np.asarray(g.astype(kept["embed"].dtype))
                 for name, g in ref.items()})

        loss = reference_loss_and_grads(kept, batch, self.cfg, keep,
                                        round_to=FP8)[0]
        params = jax.device_put(kept)
        del kept
        grads = seen.pop(())
        for stack in {w[0] for w in seen}:
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


COMPARISONS = ("selection", "sparse core", "lightning", "the step")
# The three limits of (d) that a precision moves (its selection is the
# program's own in the control too): each has to refuse fp8.
STEP_LIMITS = ("the step's loss", "the step's d_", "the step's moved_")


def main(argv=None):
    """The control on the chip: the cell's run, two seconds of window,
    with ``Fp8InTheProgramsPlace``. Exits 0 when every comparison, and
    in the step every one of its three limits, came out NOT correct, 1
    when fp8 passed one."""
    import argparse
    import json
    import time

    t0 = time.time()
    from chipbench import child
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = child.find_cell("minicpmsala.spmd.b1s32768")
    enable_compile_cache()
    lane = child.load_file("lanes", traffic["lane"]).Lane(traffic)
    lane.start()

    def say(**fields):
        print(json.dumps(fields), flush=True)

    result = child.measure(
        Fp8InTheProgramsPlace(config, traffic), lane, traffic,
        seed=args.seed, seconds=2.0, trace=False, t0=t0, say=say)
    refused = {kind: [f for f in result["faults"] if f.startswith(kind)]
               for kind in COMPARISONS + STEP_LIMITS}
    say(event="control", fp8_refused_by=refused,
        other_faults=[f for f in result["faults"]
                      if not any(f in fs for fs in refused.values())])
    lane.close()
    return 0 if all(refused.values()) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
