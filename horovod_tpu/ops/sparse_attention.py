"""Block-sparse attention over key blocks that are DATA (InfLLM-V2,
arXiv:2509.24663; MiniCPM4's ``sparse_config``, arXiv:2506.07900): the
selection, the attention over what it chose, forward and backward.

``ops/flash_attention.py`` knows every tile's class before it runs, from
the causal edge and a window. Here a token ``t`` of a key/value group
``g`` (the ``H / G`` query heads that share one key head) attends the
keys ``s <= t`` of the blocks in ``Sel(t, g)``, a set of at most ``topk``
blocks of ``block`` keys that is known only on the device. That is why
this is a file beside the flash kernels and not a mode of them: their
grid, their index maps and their three classes of tile are compile-time
facts of the causal edge, and nothing of that survives a set chosen a
token (the table is an operand, the loop over key blocks is inside the
kernel, the keys stay in VMEM whole).

The selection (:func:`select_blocks`; no parameter, no gradient), a
token ``t`` and group ``g``:

1. pooled keys ``kbar_j = mean(k[stride j : stride j + kernel])`` for
   every whole window ``j`` (the group's key head), rounded to ``k``'s
   dtype;
2. ``p[t, n, j] = softmax_j(q[t, n] . kbar_j / sqrt(d))`` over the ``j``
   whose window lies wholly at or before ``t``, in float32, a head ``n``
   of the group (the normaliser exact: the family's inference code
   approximates it from coarser keys);
3. ``P[t, g, j]`` = the sum of ``p`` over the group's heads;
4. a block ``b`` scores ``max P[t, g, j]`` over the ``block / stride +
   1`` windows ``j`` in ``[r b - 1, r b + r - 1]``, ``r = block /
   stride`` (a max-pool with padding 1), among the ``j`` that exist and
   step 2 admits; minus infinity where none does;
5. forced: the first ``init_blocks`` blocks and the last
   ``window_blocks`` begun ones, the token's own among them; ``Sel`` =
   the forced blocks and the best scoring others up to ``topk`` in all,
   every begun block where fewer have begun. Ties: the lower index.
   Nothing reads the ORDER of the chosen, so nothing is sorted
   (:func:`_largest`): the set is fixed by the row's ``topk``-th largest
   key and by how many of the keys equal to it belong. The keys' bits,
   read as integers, keep the keys' order; the ``topk``-th largest is
   the largest threshold that ``topk`` keys reach, found a bit a pass
   from the top in 32 dense compare-and-count passes whatever ``topk``
   is; the keys above it are chosen, and of the keys AT it the first by
   index until the set is full, which is a running count along the row
   and what sends a tie to the lower index (the forced keys tie by
   construction, ``init_blocks + window_blocks`` of them a row). A
   sequence of at most ``topk`` blocks needs no pass: every begun block.

What leaves the selection is the table the kernels read: for a TILE of
``TOKENS_A_TILE`` consecutive tokens and a key block, one int32 whose
bit ``r`` says whether the tile's token ``r`` chose the block, ``[B, G,
T / TOKENS_A_TILE, T / block]`` (:func:`chosen` unpacks it to the sets).

The attention (:func:`sparse_attention`), one recurrence-free softmax a
query head over exactly its token's set, on two carriers read off the
operands (``ops/_platform.py``):

- operands on a TPU: ``hvd_sparse_attn_fwd`` / ``hvd_sparse_attn_bwd``
  by their ``kernel_metadata``. A grid step is one tile: the group's
  ``H / G`` heads of ``TOKENS_A_TILE`` neighbouring tokens as the rows
  of ONE matrix (256 rows at 16 heads), against the group's keys and
  values, which stay in VMEM whole (``[T, d]`` each: 8.4 MB at T 32768,
  d 128, bf16), so a block is a dynamic slice and no gather from HBM.
  A visit is two neighbouring blocks (128 keys: a score tile's lanes
  full); the tile's row of the table arrives in SMEM. A tile first
  LISTS its walk: a scalar loop over the visits before its own writes
  those some token of the tile chose to a list in SMEM with no branch
  (every visit is stored at the list's end, the end moves on past a
  chosen one). Then it makes them with none: its OWN visit first (the
  one that holds the tile's tokens, so the only one with a causal edge;
  every token chose its own block, so from here on a row's running
  maximum is a score and a masked score's ``exp`` is 0 by itself), then
  the listed ones, ``VISITS_A_STEP`` a softmax step of the forward: as
  many ``q k^T`` products, ONE running-max update (the tiles' maximum
  element by element, then one reduction along the lanes a row), one
  rescale of ``l`` and the accumulator, one row sum (the tiles of ``p``
  added element by element in float32, then one reduction), the ``p v``
  products added up. Every visited pair is masked row by row to the
  tokens that chose each block (the block's word against the row's
  token's bit). The list's tail is padded to a whole step with visits
  whose words read 0: nobody chose them, ``p`` is 0 there. Every row
  attends its own set and nothing else; what the tile pays for is the
  UNION of its tokens' sets. The backward is one kernel over the same
  tiles and the same list, as many visits a trip of its loop:
  ``s``, ``p = exp(s - lse)``, ``dp`` and ``ds`` formed once a visited
  pair, each product written across the trip's visits (the compiler
  schedules in the program's order: visit after visit ran them one
  behind another), ``dq`` a tile, ``dk`` and ``dv`` accumulated in
  float32 in VMEM (``[T, d]`` each, a pair's slice read and written
  where its queries find it: no search for the queries that chose a
  block; a padded visit adds zeros) and written once a group;
- elsewhere: the same softmax under an explicit mask built from the
  sets, a block of query rows at a time, differentiated by autodiff
  (the CPU's path and the tests' reference for the kernels, which run
  there in interpret mode under ``_INTERPRET``).

Precision: scores, the softmax, ``lse`` and every accumulation float32;
matmul operands in the operands' dtype (``p`` and ``ds`` rounded to it
as they enter one); the selection's scores float32 from operands in
their own dtype.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import (
    _column_to_row, _pick_block, _row_to_column, _scaled)
from horovod_tpu.utils.spans import scope

F32 = jnp.float32
_NEG = -1e30
# Tests flip this to run the kernel pair in pallas interpret mode on the
# CPU (as ``flash_attention._INTERPRET``).
_INTERPRET = False
# Neighbouring tokens whose heads (a group's) make one tile's rows; a
# bit each in the table's int32.
TOKENS_A_TILE = 16
# Neighbouring blocks a visit takes side by side (where their number
# divides): two blocks of 64 keys fill the 128 lanes.
BLOCKS_A_VISIT = 2
# Listed visits a step of a tile's walk takes together: a softmax step of
# the forward (one running max, one rescale, one pass of row reductions
# for all of them) and a trip of the backward's loop (each visit whole; a
# product of one overlaps the mask and ``exp`` of the next). By the chip
# at T 32768 and 121 visits a tile (PERF.md, PR 60): 4 / 8 a step 82.1 /
# 77.2 ms a forward (16 no better), 2 / 4 / 8 a step 208.6 / 145.1 / 135.5
# a backward; a list's padded tail grows with it (2.8% of a walk at 8).
VISITS_A_STEP = 8
# Query rows a block of the selection's scores and of the explicit-mask
# form: [rows, H / G, windows] float32 at a time.
SELECT_ROWS = 512
# The kernels hold a group's keys and values whole (the backward their
# float32 gradients too: 50 MB at T 32768, d 128).
VMEM_LIMIT = 100 * 1024 * 1024


# ---------------------------------------------------------------------
# The selection, in jax.numpy on either carrier.
# ---------------------------------------------------------------------

def pooled_keys(k, kernel, stride):
    """``k`` [B, T, G, d] -> ``kbar`` [B, J, G, d] in ``k``'s dtype, ``J
    = (T - kernel) / stride + 1`` whole windows, the mean in float32."""
    B, T, G, d = k.shape
    if kernel % stride or T % stride or T < kernel:
        raise ValueError(f"pooling {kernel} keys every {stride} over {T}: "
                         "the window is no multiple of the stride, or the "
                         "sequence none, or shorter than a window")
    sums = k.astype(F32).reshape(B, T // stride, stride, G, d).sum(2)
    m, J = kernel // stride, (T - kernel) // stride + 1
    return (sum(sums[:, i:i + J] for i in range(m)) / kernel).astype(k.dtype)


def _block_scores(P, admitted, ratio, n_blocks):
    """Step 4: ``P`` [..., J] (0 where not ``admitted``) -> [...,
    n_blocks], the max over the windows ``[ratio b - 1, ratio b + ratio -
    1]`` that exist and are admitted, -inf where none."""
    P = jnp.where(admitted, P, -jnp.inf)
    lead, J = P.shape[:-1], P.shape[-1]
    # window j at index j + 1 of [-1 .. ratio n_blocks - 1]
    P = jnp.concatenate(
        [jnp.full(lead + (1,), -jnp.inf, P.dtype), P,
         jnp.full(lead + (ratio * n_blocks - J,), -jnp.inf, P.dtype)], -1)
    first = P[..., :-1].reshape(*lead, n_blocks, ratio).max(-1)
    return jnp.maximum(first, P[..., ratio::ratio])


def _rows_chosen(q, kbar, t0, *, block, topk, kernel, stride, init_blocks,
                 window_blocks, n_blocks):
    """Steps 2-5 for the query rows ``q`` [B, R, G, n, d] at positions
    ``t0 ..`` against ``kbar`` [B, J, G, d] -> bool [B, R, G, n_blocks]."""
    R, d = q.shape[1], q.shape[-1]
    t = t0 + jnp.arange(R)
    j = jnp.arange(kbar.shape[1])
    admitted = (stride * j + kernel - 1)[None, :] <= t[:, None]    # [R, J]
    s = jnp.einsum("brgnd,bjgd->brgnj", q, kbar,
                   preferred_element_type=F32) * d ** -0.5
    s = jnp.where(admitted[:, None, None, :], s, -jnp.inf)
    top = jnp.max(s, -1, keepdims=True)
    e = jnp.where(admitted[:, None, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    score = _block_scores(jnp.sum(p, 3), admitted[:, None, :],
                          block // stride, n_blocks)          # [B, R, G, nb]
    b = jnp.arange(n_blocks)
    own = (t // block)[:, None]
    begun = b <= own
    forced = begun & ((b < init_blocks) | (b > own - window_blocks))
    # forced first, then the begun by score, the not begun never; a
    # begun block with no admitted window is forced (step 4). The set is
    # the ``topk`` largest of these keys, equal keys to the lower index:
    # the forced tie at +inf and fill the set from the first block up
    # before any score is asked, begun blocks without a score tie at -1.0
    # behind every score, and -inf is never chosen.
    key = jnp.where(forced[:, None, :], jnp.inf,
                    jnp.where(begun[:, None, :], jnp.maximum(score, -1.0),
                              -jnp.inf))
    # the group beside the batch: a row's blocks along the lanes and the
    # ROWS down the sublanes (2 groups there fill a quarter of a vreg:
    # the selection alone 81 ms at the chip cell's size where this takes
    # 50, PERF.md section 6, PR 56)
    return jnp.moveaxis(_largest(jnp.moveaxis(key, 2, 1), topk), 1, 2)


def _largest(key, count):
    """bool like ``key`` [..., n] float32: which of a row's keys are among
    its ``count`` largest, of equal keys those at the lower indices, a key
    of -inf never: a top-k's set without its order, by counting (step 5
    of the module's description) where the chip's top-k sorts keys and
    indices whole."""
    n = key.shape[-1]
    if count >= n:
        return key > -jnp.inf
    # the floats' order as int32's: -inf < ... < -0.0 < +0.0 < ... < +inf
    bits = lax.bitcast_convert_type(key, jnp.int32)
    image = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

    def narrow(i, t):
        # ``t``: the largest threshold found so far that ``count`` keys
        # reach. In the order's unsigned form (the sign bit flipped) it
        # grows a bit at a time from 0, which is int32's lowest.
        higher = t ^ lax.shift_left(jnp.int32(1), 31 - i)
        reach = jnp.sum(image >= higher[..., None], -1, dtype=jnp.int32)
        return jnp.where(reach >= count, higher, t)

    lowest = jnp.full(key.shape[:-1], jnp.iinfo(jnp.int32).min, jnp.int32)
    t = lax.fori_loop(0, 32, narrow, lowest)[..., None]
    above, tied = image > t, image == t
    # A key's place among its row's tied ones, itself counted: a product
    # with a triangle of ones on the MXU (0 / 1 operands and sums up to
    # ``n`` are exact). The first ``count - above`` of them fill the set.
    upto = jnp.tri(n, dtype=jnp.bfloat16).T             # [s, b]: s <= b
    place = jnp.einsum("...s,sb->...b", tied.astype(jnp.bfloat16), upto,
                       preferred_element_type=F32)
    room = count - jnp.sum(above, -1, keepdims=True, dtype=jnp.int32)
    return (above | (tied & (place <= room.astype(F32)))) \
        & (key > -jnp.inf)


def _pack(chosen):
    """bool [B, T, G, nb] -> the kernels' table, int32 [B, G, T /
    TOKENS_A_TILE, nb]: bit ``r`` of a word, the tile's token ``r``."""
    B, T, G, nb = chosen.shape
    R = TOKENS_A_TILE
    bits = chosen.reshape(B, T // R, R, G, nb).astype(jnp.int32) \
        << jnp.arange(R, dtype=jnp.int32)[:, None, None]
    return jnp.moveaxis(jnp.sum(bits, 2), 2, 1)


def chosen(table):
    """The table -> the sets, bool [B, T, G, nb]."""
    B, G, tiles, nb = table.shape
    R = TOKENS_A_TILE
    bits = (table[:, :, :, None, :] >> jnp.arange(
        R, dtype=jnp.int32)[:, None]) & 1                 # [B, G, tiles, R, nb]
    return jnp.moveaxis(bits.astype(bool), 1, 3).reshape(B, tiles * R, G, nb)


def select_blocks(q, k, *, block, topk, kernel, stride, init_blocks,
                  window_blocks):
    """Steps 1-5 of the module's description for ``q`` [B, T, H, d] and
    ``k`` [B, T, G, d] (after their norms; query head ``h`` reads key head
    ``h // (H / G)``) -> the table, int32 [B, G, T / TOKENS_A_TILE, T /
    block]. No gradient passes (``stop_gradient``)."""
    B, T, H, d = q.shape
    G = k.shape[2]
    if T % block or T % TOKENS_A_TILE or block % stride or H % G:
        raise ValueError(
            f"a sequence of {T} in blocks of {block} keys, tiles of "
            f"{TOKENS_A_TILE} tokens, strides of {stride}; {H} heads on "
            f"{G}: each has to divide")
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)
    kbar = pooled_keys(k, kernel, stride)
    rows = _pick_block(T, SELECT_ROWS)
    sizes = dict(block=block, topk=topk, kernel=kernel, stride=stride,
                 init_blocks=init_blocks, window_blocks=window_blocks,
                 n_blocks=T // block)
    qs = jnp.moveaxis(q.reshape(B, T // rows, rows, G, H // G, d), 1, 0)
    sel = lax.map(lambda x: _rows_chosen(x[0], kbar, x[1], **sizes),
                  (qs, jnp.arange(T // rows) * rows))
    return _pack(jnp.moveaxis(sel, 0, 1).reshape(B, T, G, T // block))


# ---------------------------------------------------------------------
# The attention under an explicit mask: the CPU's path.
# ---------------------------------------------------------------------

def _masked_rows(q, k, v, sel, t0, block):
    """``q`` [B, R, G, n, d] at positions ``t0 ..``, ``k``, ``v`` [B, T,
    G, d], ``sel`` bool [B, R, G, nb] -> [B, R, G, n, d]."""
    R, T, d = q.shape[1], k.shape[1], q.shape[-1]
    t, s_at = t0 + jnp.arange(R), jnp.arange(T)
    allowed = jnp.repeat(sel, block, -1) \
        & (s_at[None, :] <= t[:, None])[None, :, None, :]   # [B, R, G, T]
    s = jnp.einsum("brgnd,bsgd->brgns", _scaled(q, d ** -0.5), k,
                   preferred_element_type=F32)
    s = jnp.where(allowed[:, :, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, -1).astype(v.dtype)
    return jnp.einsum("brgns,bsgd->brgnd", p, v,
                      preferred_element_type=F32).astype(q.dtype)


def _masked_attention(q, k, v, table, block):
    B, T, H, d = q.shape
    G = k.shape[2]
    rows = _pick_block(T, SELECT_ROWS)
    sel = chosen(table)

    def lead(a, *rest):
        return jnp.moveaxis(a.reshape(B, T // rows, rows, *rest), 1, 0)

    o = lax.map(
        jax.checkpoint(lambda x: _masked_rows(x[0], k, v, x[1], x[2],
                                              block)),
        (lead(q, G, H // G, d), lead(sel, G, T // block),
         jnp.arange(T // rows) * rows))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, d)


# ---------------------------------------------------------------------
# The Pallas TPU kernel pair: a group's keys and values in VMEM.
# ---------------------------------------------------------------------

def _token_bits(rows, n, width):
    """[rows, width] int32: row ``r`` is head ``r % n`` of the tile's
    token ``r // n``; that token's bit of a table word."""
    return lax.shift_left(jnp.int32(1), lax.broadcasted_iota(
        jnp.int32, (rows, width), 0) // n)


def _allowed(words, bit, block, edge=None):
    """[rows, len(words) block] bool for a VISIT, ``len(words)``
    neighbouring blocks side by side along the lanes (two: 128 keys fill
    a vreg's lanes where one block of 64 fills half): a row (``bit``:
    :func:`_token_bits`) sees a key where its token chose the key's block
    (its bit of that block's word) and, in the tile's own visit, the key
    is not after it (``edge``: :func:`_not_after`)."""
    col = lax.broadcasted_iota(jnp.int32, (1, bit.shape[1]), 1)
    word = jnp.full(col.shape, words[0], jnp.int32)
    for i in range(1, len(words)):
        word = jnp.where(col >= i * block, words[i], word)
    ok = (word & bit) != 0
    return ok if edge is None else ok & edge


def _not_after(rows, n, width, behind):
    """[rows, width] bool, the causal edge in a tile's OWN visit, whose
    first key lies ``behind`` keys behind the tile's first token: the
    keys at or before a row's token (a listed visit lies wholly before
    the tile and has no edge)."""
    shape = (rows, width)
    return lax.broadcasted_iota(jnp.int32, shape, 1) <= behind \
        + lax.broadcasted_iota(jnp.int32, shape, 0) // n


def _words(table_ref, visit, per):
    """The table's words of the ``per`` blocks of ``visit``."""
    return [table_ref[0, visit * per + i] for i in range(per)]


def _list_visits(table_ref, list_ref, own, per):
    """Write the visits before ``own`` that some token of the tile chose
    to ``list_ref`` (SMEM), in order and without a branch (every visit
    is stored at the list's end, and the end moves on past a chosen
    one), then visits 0 up to a whole step; -> how many are listed."""
    def note(b, count):
        words = _words(table_ref, b, per)
        list_ref[count] = b
        return count + (functools.reduce(jnp.bitwise_or, words) != 0
                        ).astype(jnp.int32)

    count = lax.fori_loop(0, own, note, jnp.int32(0))
    for i in range(VISITS_A_STEP - 1):
        list_ref[count + i] = 0
    return count


def _listed(table_ref, list_ref, at, count, per):
    """The list's entry ``at`` -> (the visit, its words); a padded entry
    (at or past ``count``) reads words of 0: nobody chose it."""
    visit = list_ref[at]
    return visit, [jnp.where(at < count, w, 0)
                   for w in _words(table_ref, visit, per)]


def _fwd_kernel(table_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, list_ref,
                *, n, block, per, scale):
    """One tile: ``q_ref`` [rows, d] (token-major, a token's ``n`` heads
    side by side down the rows), ``k_ref``, ``v_ref`` [T, d], its row of
    the table in SMEM -> ``o_ref`` [rows, d], ``lse_ref`` [1, rows];
    ``list_ref``: the tile's list of visits (SMEM scratch)."""
    tile = pl.program_id(2)
    rows, d = q_ref.shape
    qs = _scaled(q_ref[...], scale)
    width = per * block
    bit = _token_bits(rows, n, width)
    own = tile * TOKENS_A_TILE // width
    count = _list_visits(table_ref, list_ref, own, per)

    def scores(visit, words, edge=None):
        at = pl.ds(pl.multiple_of(visit * width, width), width)
        s = lax.dot_general(qs, k_ref[at, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)
        return at, jnp.where(_allowed(words, bit, block, edge), s, _NEG)

    def weighted(p, at):
        return jnp.dot(p.astype(v_ref.dtype), v_ref[at, :],
                       preferred_element_type=F32)

    # The tile's own visit first: every row finds its token's own key
    # there, so ``m`` is a score from here on and a masked score's
    # ``exp(_NEG - m)`` is 0 with no second select.
    at, s = scores(own, _words(table_ref, own, per), _not_after(
        rows, n, width, tile * TOKENS_A_TILE - own * width))
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - m)
    carry = m, jnp.sum(p, -1, keepdims=True), weighted(p, at)

    def step(i, carry):
        m, l, acc = carry
        ats, ss = zip(*(
            scores(*_listed(table_ref, list_ref, i * VISITS_A_STEP + j,
                            count, per)) for j in range(VISITS_A_STEP)))
        m_new = jnp.maximum(m, jnp.max(functools.reduce(jnp.maximum, ss),
                                       -1, keepdims=True))
        a = jnp.exp(m - m_new)
        ps = [jnp.exp(s - m_new) for s in ss]
        return (m_new,
                a * l + jnp.sum(functools.reduce(jnp.add, ps), -1,
                                keepdims=True),
                a * acc + functools.reduce(jnp.add, map(weighted, ps, ats)))

    m, l, acc = lax.fori_loop(0, pl.cdiv(count, VISITS_A_STEP), step, carry)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = _column_to_row(m + jnp.log(l))


def _bwd_kernel(table_ref, q_ref, k_hbm, v_hbm, do_ref, lse_ref, delta_ref,
                dq_ref, dk_hbm, dv_hbm, k_ref, v_ref, dk_ref, dv_ref, sem,
                list_ref, *, n, block, per, scale):
    """One tile of the backward. The group's keys and values are copied
    into VMEM once a group (``k_ref``, ``v_ref``: scratch, one buffer
    each), their float32 gradients gathered there (``dk_ref``,
    ``dv_ref``) and copied out behind the group's last tile."""
    bi, g, tile = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows, d = q_ref.shape

    def copies(pairs):
        return [pltpu.make_async_copy(src, dst, sem.at[i])
                for i, (src, dst) in enumerate(pairs)]

    @pl.when(tile == 0)
    def _start():
        loads = copies([(k_hbm.at[bi, g], k_ref), (v_hbm.at[bi, g], v_ref)])
        for c in loads:
            c.start()
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        for c in loads:
            c.wait()

    q, do = q_ref[...], do_ref[...]
    qs = _scaled(q, scale)
    lse, delta = _row_to_column(lse_ref[...]), _row_to_column(delta_ref[...])
    width = per * block
    bit = _token_bits(rows, n, width)
    own = tile * TOKENS_A_TILE // width
    count = _list_visits(table_ref, list_ref, own, per)

    def attend(visits, edge=None):
        """The part of ``dq`` of ``visits`` [(visit, words), ...], a
        product after another ACROSS them (the scheduler follows the
        program's order: visit by visit it ran them one behind another);
        their ``dk``, ``dv`` added in place (a padded visit adds zeros)."""
        ats = [pl.ds(pl.multiple_of(b * width, width), width)
               for b, _ in visits]
        ss = [lax.dot_general(qs, k_ref[at, :], (((1,), (1,)), ((), ())),
                              preferred_element_type=F32) for at in ats]
        dps = [lax.dot_general(do, v_ref[at, :], (((1,), (1,)), ((), ())),
                               preferred_element_type=F32) for at in ats]
        ps = [jnp.where(_allowed(words, bit, block, edge),
                        jnp.exp(s - lse), 0.0)
              for s, (_, words) in zip(ss, visits)]
        dss = [(p * (dp - delta)).astype(q.dtype) for p, dp in zip(ps, dps)]
        across = (((0,), (0,)), ((), ()))               # over the rows
        for at, p in zip(ats, ps):
            dv_ref[at, :] += lax.dot_general(
                p.astype(do.dtype), do, across, preferred_element_type=F32)
        for at, ds in zip(ats, dss):
            dk_ref[at, :] += lax.dot_general(
                ds, qs, across, preferred_element_type=F32)
        return functools.reduce(jnp.add, [
            jnp.dot(ds, k_ref[at, :], preferred_element_type=F32)
            for at, ds in zip(ats, dss)])

    def trip(i, dq):
        return dq + attend([
            _listed(table_ref, list_ref, i * VISITS_A_STEP + j, count, per)
            for j in range(VISITS_A_STEP)])

    dq = lax.fori_loop(
        0, pl.cdiv(count, VISITS_A_STEP), trip,
        attend([(own, _words(table_ref, own, per))], _not_after(
            rows, n, width, tile * TOKENS_A_TILE - own * width)))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(tile == pl.num_programs(2) - 1)
    def _finish():
        stores = copies([(dk_ref, dk_hbm.at[bi, g]),
                         (dv_ref, dv_hbm.at[bi, g])])
        for c in stores:
            c.start()
        for c in stores:
            c.wait()


def _blocks_a_visit(n_blocks, block):
    """Neighbouring blocks a visit takes together. A tile's tokens have
    to lie in ONE visit, its own (the only one with a causal edge)."""
    per = BLOCKS_A_VISIT if n_blocks % BLOCKS_A_VISIT == 0 else 1
    if per * block % TOKENS_A_TILE:
        raise ValueError(
            f"visits of {per * block} keys under tiles of {TOKENS_A_TILE} "
            "tokens: a tile has to lie in one visit")
    return per


def _list_scratch(n_blocks, per):
    """A tile's list of visits: at most every visit before its own, and
    the padding to a whole step."""
    return pltpu.SMEM((n_blocks // per + VISITS_A_STEP,), jnp.int32)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _tile_specs(rows, d, nb):
    """A tile's blocks of the table [B, G, tiles, 1, nb] (SMEM), of a
    row-major operand [B, G, tiles * rows, d] and of a row statistic [B,
    G, tiles, 1, rows]."""
    return (pl.BlockSpec((None, None, None, 1, nb),
                         lambda b, g, i: (b, g, i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, rows, d), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((None, None, None, 1, rows),
                         lambda b, g, i: (b, g, i, 0, 0)))


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def _kernel_fwd(table, q, k, v, *, n, block, interpret):
    """``table`` [B, G, tiles, nb]; ``q`` [B, G, T n, d] (token-major);
    ``k``, ``v`` [B, G, T, d] -> (``o`` like ``q``, ``lse`` [B, G, tiles,
    1, rows] float32). Jitted on its own: every site that enters it with
    these shapes calls ONE lowered function."""
    B, G, tiles, nb = table.shape
    T, d = k.shape[2:]
    rows = TOKENS_A_TILE * n
    word, tile, stat = _tile_specs(rows, d, nb)
    per = _blocks_a_visit(nb, block)
    whole = pl.BlockSpec((None, None, T, d), lambda b, g, i: (b, g, 0, 0))
    with scope("hvd.sparse.core"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, n=n, block=block, per=per,
                              scale=d ** -0.5),
            grid=(B, G, tiles), in_specs=[word, tile, whole, whole],
            out_specs=[tile, stat],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((B, G, tiles, 1, rows), F32)],
            scratch_shapes=[_list_scratch(nb, per)],
            interpret=interpret,
            metadata={"kernel": "hvd_sparse_attn_fwd"},
            compiler_params=_params(),
        )(table[:, :, :, None, :], q, k, v)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def _kernel_bwd(table, q, k, v, o, lse, do, *, n, block, interpret):
    """-> (dq like ``q``, dk, dv like ``k`` in float32)."""
    B, G, tiles, nb = table.shape
    T, d = k.shape[2:]
    rows = TOKENS_A_TILE * n
    word, tile, stat = _tile_specs(rows, d, nb)
    per = _blocks_a_visit(nb, block)
    with scope("hvd.sparse.core"):
        delta = jnp.sum(do.astype(F32) * o.astype(F32), -1).reshape(
            lse.shape)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        kv32 = jax.ShapeDtypeStruct(k.shape, F32)
        return pl.pallas_call(
            functools.partial(_bwd_kernel, n=n, block=block, per=per,
                              scale=d ** -0.5),
            grid=(B, G, tiles),
            in_specs=[word, tile, hbm, hbm, tile, stat, stat],
            out_specs=[tile, hbm, hbm],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), kv32, kv32],
            scratch_shapes=[pltpu.VMEM((T, d), k.dtype),
                            pltpu.VMEM((T, d), v.dtype),
                            pltpu.VMEM((T, d), F32),
                            pltpu.VMEM((T, d), F32),
                            pltpu.SemaphoreType.DMA((2,)),
                            _list_scratch(nb, per)],
            interpret=interpret,
            metadata={"kernel": "hvd_sparse_attn_bwd"},
            compiler_params=_params(),
        )(table[:, :, :, None, :], q, k, v, do, lse, delta)


def _token_major(x, G):
    """[B, T, H, d] -> [B, G, T H / G, d]: a token's heads of a group
    side by side down the rows."""
    B, T, H, d = x.shape
    return jnp.moveaxis(x.reshape(B, T, G, H // G * d), 2, 1).reshape(
        B, G, T * (H // G), d)


def _head_minor(x, T):
    """``_token_major``'s inverse -> [B, T, H, d]."""
    B, G, _, d = x.shape
    return jnp.moveaxis(x.reshape(B, G, T, -1), 1, 2).reshape(B, T, -1, d)


def _group_major(x):
    return jnp.moveaxis(x, 2, 1)            # [B, T, G, d] <-> [B, G, T, d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel_attention(q, k, v, table, block):
    return _kernel_attention_fwd(q, k, v, table, block)[0]


def _kernel_attention_fwd(q, k, v, table, block):
    G = k.shape[2]
    how = {"n": q.shape[2] // G, "block": block, "interpret": _INTERPRET}
    qt, kt, vt = _token_major(q, G), _group_major(k), _group_major(v)
    o, lse = _kernel_fwd(table, qt, kt, vt, **how)
    # named as the flash kernels' residuals: what a remat policy saves so
    # that the backward does not run the forward kernel again
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return _head_minor(o, q.shape[1]), (qt, kt, vt, table, o, lse)


def _kernel_attention_bwd(block, res, do):
    qt, kt, vt, table, o, lse = res
    G, T = kt.shape[1], kt.shape[2]
    dq, dk, dv = _kernel_bwd(
        table, qt, kt, vt, o, lse, _token_major(do.astype(qt.dtype), G),
        n=qt.shape[2] // T, block=block, interpret=_INTERPRET)
    return (_head_minor(dq, T), _group_major(dk).astype(kt.dtype),
            _group_major(dv).astype(vt.dtype), None)


_kernel_attention.defvjp(_kernel_attention_fwd, _kernel_attention_bwd)


def sparse_attention(q, k, v, table, block):
    """``o`` [B, T, H, d]: query head ``h`` at token ``t`` attends, under
    ONE softmax with scores ``q . k / sqrt(d)``, the keys ``s <= t`` of
    the blocks (``block`` keys each) its token chose for its group ``h //
    (H / G)``, as ``table`` (:func:`select_blocks`) has them. ``q`` [B,
    T, H, d], ``k``, ``v`` [B, T, G, d]. Every token has to have chosen
    a block that holds a key at or before it (its own: the selection
    forces it). Differentiable in ``q``, ``k``, ``v``."""
    if use_pallas("sparse_attention", (q, k, v), _INTERPRET):
        return _kernel_attention(q, k, v, table, block)
    return _masked_attention(q, k, v, table, block)
