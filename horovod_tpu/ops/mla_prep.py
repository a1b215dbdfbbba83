"""Latent attention's seam between the up-projections and the flash
kernels (``models/llama.py:_latent_attention``), ONE pass over HBM
forward and one backward: the rotation of ``q_r`` and of the shared
``k_r``, the assembly of ``q`` and ``k``, ``v`` cut out of ``kv`` and
the split into heads.

From ``yq`` [B, T, H (dn + dr)] (a head ``[q_n | q_r]``) and ``ykv``
[B, T, H (dn + dv)] (a head ``[k_n | v]``) as ``c_q @ wq_b`` and ``c_kv @
wkv_b`` leave them (no reshape into heads in front: ``ops/qk_prep.py``
says why), ``k_r`` [B, T, dr] and the rotation's table, to ``q``, ``k``
[B, H, T, dn + dr] and ``v`` [B, H, T, dv] as ``_flash`` takes them:
``q[j] = [q_n | RoPE(q_r)]``, ``k[j] = [k_n | RoPE(k_r)]`` with the SAME
rotated ``k_r`` in every head. What the expressions of
``_latent_attention`` do in a dozen passes (two ``_rope`` calls, two
concatenations, a broadcast, a strided slice, ``flash_attention``'s three
transposes), and what the kernels are tested against.

A grid step ``(batch, block of tokens)`` holds its tokens at full width,
rotates ``k_r`` once (float32, ONE rounding where ``_rope`` has three; the
half-split rotation as a lane roll by ``dr / 2`` and two multiply-adds,
``qk_prep._tables``) and walks the heads. A head of ``yq`` is ``dn + dr``
lanes wide, so it starts on a lane tile's edge only every ``_group`` heads
(192 wide: two heads are three tiles): the walk takes such a group of
heads a turn, its slab read at a tile's edge whatever the loop's counter
and cut by offsets the compiler knows. The output block ``[heads,
tokens, width]`` carries the head in its index, so the broadcast, the
concatenations, ``v``'s slice and the transposes cost nothing beyond the
stores. The table ``[B, T, dr]`` float32 holds ``[cos, sin]`` of a token's
angles times ``mult`` and is made outside from the positions
(:func:`rotation_table`):

- ``hvd_mla_prep_fwd``: grid ``(B, T / bt)``, both parallel.
- ``hvd_mla_prep_bwd``: the same grid; takes ``dq``, ``dk``, ``dv``
  head-major as the flash backward leaves them, writes ``dyq`` and
  ``dykv`` in the projections' layout, what the up-projections' backward
  matmuls read, and ``dk_r`` = ``RoPE^T`` of the SUM over the heads of
  ``dk``'s rotated slices, summed in float32 in VMEM.

Behind a ``custom_vjp`` that saves the table and nothing else (the
rotation's transpose reads no input). The names are the calls'
``kernel_metadata``, what a device trace shows; each kernel sits behind
ONE jitted function under the scope ``hvd.mla.core``, so a program pays
one Mosaic lowering a kernel whatever the number of layers.
:func:`on_kernels` reads the carrier off the operands
(``ops/_platform.py``); elsewhere the caller's expressions run.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu.ops._platform import use_pallas
from horovod_tpu.ops.flash_attention import _kernel_mesh_specs
from horovod_tpu.ops.qk_prep import (_LANES, _PACKED, _call, _slab, _step,
                                     _swap, _tables, _walk)
from horovod_tpu.utils.spans import scope

F32 = jnp.float32
# Tests flip this to run the kernels in pallas interpret mode on the CPU
# (as ``flash_attention._INTERPRET``).
_INTERPRET = False
# What one grid step takes: so many tokens (or the largest divisor of
# the sequence under them) at full width.
TOKENS_A_STEP = 256


def _group(width):
    """So many heads ``width`` lanes wide fill whole lane tiles."""
    return _LANES // math.gcd(width, _LANES)


def on_kernels(x, heads, dn, dr, dv):
    """True where the seam runs as the kernel pair: the layer's input
    ``x`` [B, T, D] on a TPU (or ``_INTERPRET``, the tests' switch),
    whole packed tiles of tokens, ``q_n`` / ``k_n`` (``dn`` wide) and
    ``v`` (``dv``) whole 128-lane slabs, a rotated slice (``dr``) that
    divides a slab and heads that come in whole groups (``_group``).
    (Latent attention runs over no sequence axis: its caller refuses
    one.)"""
    return (dn % _LANES == 0 and dv % _LANES == 0
            and _LANES % dr == 0 and dr % 2 == 0
            and heads % _group(dn + dr) == 0
            and x.shape[1] % _PACKED == 0
            and use_pallas("mla_prep", (x,), _INTERPRET))


def rotation_table(positions, freqs, mult):
    """``positions`` [B, T], the frequencies [dr / 2] and the factor of
    ``LlamaConfig.yarn()`` -> float32 [B, T, dr]: ``[cos, sin]`` of each
    token's angles times ``mult``, ``_rope``'s in float32."""
    angles = positions[:, :, None].astype(F32) * jnp.asarray(freqs, F32)
    return jnp.concatenate([jnp.cos(angles), jnp.sin(angles)], -1) * mult


def _fwd_kernel(yq_ref, ykv_ref, kr_ref, table_ref, q_ref, k_ref, v_ref, *,
                dn):
    """A loop over groups of heads (``_walk``): a group's slab of ``yq``
    cut into its heads' ``[q_n | q_r]``, each head's ``[k_n | v]`` of
    ``ykv``, the one rotated ``k_r`` behind every ``k_n``."""
    heads, _, w = q_ref.shape
    per = _group(w)
    cos, sin = _tables(table_ref[...])

    def turned(x):
        x = x.astype(F32)
        return (x * cos + _swap(x) * sin).astype(q_ref.dtype)

    k_r = turned(kr_ref[...])

    def group(g, carry):
        yq = yq_ref[:, _slab(g, per * w)]
        for i in range(per):
            j, at = g * per + i, i * w
            q_ref[j, :, :dn] = yq[:, at:at + dn]
            q_ref[j, :, dn:] = turned(yq[:, at + dn:at + w])
            ykv = ykv_ref[:, _slab(j, dn + v_ref.shape[2])]
            k_ref[j, :, :dn] = ykv[:, :dn]
            k_ref[j, :, dn:] = k_r
            v_ref[j] = ykv[:, dn:]
        return carry

    _walk(heads // per, group, 0)


def _bwd_kernel(dq_ref, dk_ref, dv_ref, table_ref, dyq_ref, dykv_ref,
                dkr_ref, *, dn):
    """The same walk backward; the carry is the sum over the heads of
    ``dk``'s rotated slices, float32 ``[tokens, dr]``."""
    heads, bt, w = dq_ref.shape
    per = _group(w)
    cos, sin = _tables(table_ref[...])

    def turned_back(g):
        return g * cos - _swap(g) * sin

    def group(g, total):
        dyq = []
        for i in range(per):
            j = g * per + i
            dyq += [dq_ref[j, :, :dn],
                    turned_back(dq_ref[j, :, dn:].astype(F32))
                    .astype(dyq_ref.dtype)]
            dykv_ref[:, _slab(j, dn + dv_ref.shape[2])] = jnp.concatenate(
                [dk_ref[j, :, :dn], dv_ref[j]], 1)
            total = total + dk_ref[j, :, dn:].astype(F32)
        dyq_ref[:, _slab(g, per * w)] = jnp.concatenate(dyq, 1)
        return total

    total = _walk(heads // per, group, jnp.zeros((bt, w - dn), F32))
    dkr_ref[...] = turned_back(total).astype(dkr_ref.dtype)


def _specs(B, T, H, dn, dr, dv, bt, itemsize):
    """(grid; the blocks of ``yq``, ``ykv``, ``k_r`` and the table; the
    blocks of ``q``, ``k``, ``v``; the bytes a step's blocks take in
    VMEM, where a row lies in whole lane tiles: a 192-wide one 256)."""
    w = dn + dr

    def flat(width):
        return pl.BlockSpec((None, bt, width), lambda b, t: (b, t, 0))

    def heads(width):
        return pl.BlockSpec((None, H, bt, width), lambda b, t: (b, 0, t, 0))

    tiles = -(-w // _LANES) * _LANES
    values = H * (w + dn + dv) + H * (2 * tiles + dv) + _LANES
    return ((B, T // bt), [flat(H * w), flat(H * (dn + dv)), flat(dr),
                           flat(dr)], [heads(w), heads(w), heads(dv)],
            bt * (values * itemsize + 4 * _LANES))


@functools.partial(jax.jit, static_argnames=("dn", "bt", "interpret"))
def _fwd(yq, ykv, k_r, table, *, dn, bt, interpret):
    """-> ``q``, ``k`` [B, H, T, dn + dr], ``v`` [B, H, T, dv]. Jitted on
    its own, and the scope again, as ``gated_delta_rule._kernel_fwd`` has
    it and says why."""
    with scope("hvd.mla.core"):
        B, T, dr = table.shape
        H = yq.shape[2] // (dn + dr)
        dv = ykv.shape[2] // H - dn
        grid, flat, heads, block_bytes = _specs(B, T, H, dn, dr, dv, bt,
                                                yq.dtype.itemsize)
        return _call(
            "hvd_mla_prep_fwd", functools.partial(_fwd_kernel, dn=dn),
            (yq, ykv, k_r, table), grid, flat, heads,
            [jax.ShapeDtypeStruct((B, H, T, w), yq.dtype)
             for w in (dn + dr, dn + dr, dv)], block_bytes, interpret)


@functools.partial(jax.jit, static_argnames=("dn", "bt", "interpret"))
def _bwd(table, dq, dk, dv, *, dn, bt, interpret):
    """-> ``dyq`` [B, T, H (dn + dr)], ``dykv`` [B, T, H (dn + dv)] and
    ``dk_r`` [B, T, dr]."""
    with scope("hvd.mla.core"):
        B, H, T, w = dq.shape
        width = dv.shape[3]
        grid, flat, heads, block_bytes = _specs(B, T, H, dn, w - dn, width,
                                                bt, dq.dtype.itemsize)
        return _call(
            "hvd_mla_prep_bwd", functools.partial(_bwd_kernel, dn=dn),
            (dq, dk, dv, table), grid, heads + flat[3:], flat[:3],
            [jax.ShapeDtypeStruct((B, T, n), dq.dtype)
             for n in (H * w, H * (dn + width), w - dn)],
            block_bytes, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel(yq, ykv, k_r, table, dn):
    return _fwd(yq, ykv, k_r, table, dn=dn,
                **_step(yq.shape[1], TOKENS_A_STEP, _INTERPRET))


def _kernel_fwd(yq, ykv, k_r, table, dn):
    # The rotation's transpose reads no input: the table and nothing else.
    return _kernel(yq, ykv, k_r, table, dn), table


def _kernel_bwd(dn, table, grads):
    # The table is made of positions: nothing reads its cotangent.
    return (*_bwd(table, *grads, dn=dn,
                  **_step(table.shape[1], TOKENS_A_STEP, _INTERPRET)),
            jnp.zeros_like(table))


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def mla_prep(yq, ykv, k_r, positions, freqs, mult, dn, mesh=None):
    """``yq`` [B, T, H (dn + dr)], ``ykv`` [B, T, H (dn + dv)] and
    ``k_r`` [B, T, dr] in the compute dtype as the projections leave them
    -> ``q``, ``k`` [B, H, T, dn + dr], ``v`` [B, H, T, dv]: ``q_r`` a
    head and ``k_r`` turned by ``positions`` [B, T] at ``freqs``
    [dr / 2] times ``mult``. Differentiable in the three.

    ``mesh``: as ``qk_prep``'s: batch over ``data`` / ``fsdp``, heads in
    whole groups over ``tensor`` (``dk_r``, every head's, is then summed
    over it), each device on its own shard."""
    from jax.sharding import PartitionSpec as P

    table = rotation_table(positions, freqs, mult)

    def run(*operands):
        return _kernel(*operands, dn)

    if mesh is None or mesh.size == 1:
        return run(yq, ykv, k_r, table)
    w = dn + k_r.shape[2]
    groups = yq.shape[2] // w // _group(w)
    batch, heads = _kernel_mesh_specs(mesh, yq.shape[0], groups, groups)
    flat, whole = P(batch, None, heads), P(batch, None, None)
    major = P(batch, heads, None, None)
    return jax.shard_map(
        run, mesh=mesh, in_specs=(flat, flat, whole, whole),
        out_specs=(major, major, major), check_vma=False,
    )(yq, ykv, k_r, table)
