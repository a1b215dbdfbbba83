"""What guards the chip path without a chip.

1. Compile-only, for a *described* ``v5e:2x2`` device (the TPU compiler
   is installed here; no chip is attached): the main path's kernels at
   their real widths must lower to ``tpu_custom_call`` and be accepted by
   the chip's compiler — tiling, VMEM and alignment faults that
   interpret mode cannot see fail here, at no chip time. Nothing runs;
   this says nothing about results or speed.
2. ``chip_smoke.py`` without a TPU must fail and print no result.
3. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
   to the fixed in-checkout path.
4. Peak tables know this chip and refuse what they do not know.
"""

import base64
import functools
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import _platform

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described (not attached) v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / old jax here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(v5e_chip, monkeypatch):
    """Steer the kernel wrappers onto their TPU branch (tracers for a
    described device still report the CPU backend), with the persistent
    compile cache off: an executable compiled for an unattached chip can
    be written to it but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def executable(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    def compile_for_chip(fn, *shapes):
        return executable(fn, *shapes).as_text()

    compile_for_chip.executable = executable
    yield compile_for_chip
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# The flagship's attention shape: B4 T2048 H16 Hkv4 D128.
_Q, _KV = ((4, 2048, 16, 128), BF16), ((4, 2048, 4, 128), BF16)


def _flash_fwd(q, k, v):
    from horovod_tpu.ops import flash_attention

    return flash_attention(q, k, v, causal=True)


def _flash_fwd_bwd(q, k, v):
    return jax.grad(lambda *a: _flash_fwd(*a).astype(F32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _flash_window_fwd_bwd(q, k, v):
    from horovod_tpu.ops import flash_attention

    return jax.grad(lambda *a: flash_attention(
        *a, causal=True, window=2048).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _flash_latent_fwd_bwd(q, k, v):
    """Latent attention's call: values another width than queries and
    keys, the softmax scale handed in (Xing4.0: ``m^2 / sqrt(192)``)."""
    from horovod_tpu.ops import flash_attention

    return jax.grad(lambda *a: flash_attention(
        *a, causal=True, scale=1.4159 ** 2 / 192 ** 0.5).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _flash_chunk(q, k, v, q_off, kv_off):
    # One ring-attention step, kernel layout [B, H, T, D], with traced
    # global offsets; the lse cotangent exercises the folded backward.
    from horovod_tpu.ops.flash_attention import flash_attention_chunk

    def f(q, k, v):
        o, lse = flash_attention_chunk(q, k, v, q_off, kv_off)
        return o.astype(F32).sum() + lse.sum()

    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


def _flash_biased(q, k, v, bias):
    from horovod_tpu.ops import flash_attention

    return jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=False, kv_bias=bias).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _decode(q, ck, cv, pos):
    from horovod_tpu.ops.decode_attention import decode_attention

    return decode_attention(q, ck, cv, pos)


def _gmm(lhs, rhs, group_sizes):
    from horovod_tpu.ops.grouped_moe import _grouped_mm

    return jax.grad(lambda lhs, rhs: _grouped_mm(
        lhs, rhs, group_sizes).astype(F32).sum(), argnums=(0, 1))(lhs, rhs)


def _gmm_layer_1_of_2(lhs, stack, group_sizes):
    """The grouped GEMM as an unrolled expert stack calls it: the whole
    stack and a layer, not a slice."""
    from horovod_tpu.ops.grouped_moe import LayerOfStack

    return _gmm(lhs, LayerOfStack(stack, 1), group_sizes)


# Trinity-Mini's attention at the chip cell's size: B2 T8192 H32 Hkv4.
_TQ, _TKV = ((2, 8192, 32, 128), BF16), ((2, 8192, 4, 128), BF16)


@pytest.mark.parametrize("fn,shapes", [
    pytest.param(_flash_fwd, (_Q, _KV, _KV), id="flash-fwd-flagship"),
    pytest.param(_flash_fwd_bwd, (_Q, _KV, _KV),
                 id="flash-fwd+bwd-flagship"),
    # The chip cells' attention shapes (and the 8k and 16k sequences of
    # PERF.md section 7). No
    # compiler option rides along here, so a kernel that needs more VMEM
    # than the compiler's default scope has to ask for it itself.
    pytest.param(_flash_fwd_bwd,
                 (((2, 4096, 32, 128), BF16),)
                 + (((2, 4096, 8, 128), BF16),) * 2,
                 id="flash-fwd+bwd-mistral7b-b2s4096"),
    pytest.param(_flash_fwd_bwd, (((2, 4096, 16, 128), BF16),) * 3,
                 id="flash-fwd+bwd-olmoe1b7b-b2s4096"),
    pytest.param(_flash_fwd_bwd,
                 (((1, 8192, 32, 128), BF16),)
                 + (((1, 8192, 8, 128), BF16),) * 2,
                 id="flash-fwd+bwd-mistral7b-b1s8192"),
    pytest.param(_flash_fwd_bwd,
                 (((1, 16384, 16, 128), BF16),)
                 + (((1, 16384, 4, 128), BF16),) * 2,
                 id="flash-fwd+bwd-long-context-t16384"),
    # Trinity-Mini's window layers (window 2048) and its full layer.
    pytest.param(_flash_window_fwd_bwd, (_TQ, _TKV, _TKV),
                 id="flash-window-fwd+bwd-trinitymini-b2s8192"),
    pytest.param(_flash_fwd_bwd, (_TQ, _TKV, _TKV),
                 id="flash-fwd+bwd-trinitymini-b2s8192"),
    pytest.param(_flash_chunk,
                 (((1, 16, 2048, 128), BF16), ((1, 4, 2048, 128), BF16),
                  ((1, 4, 2048, 128), BF16), ((), I32), ((), I32)),
                 id="flash-chunk-offsets"),
    # BERT-base attention over a padded batch: B32 T512 H12 D64.
    pytest.param(_flash_biased,
                 (((32, 512, 12, 64), BF16),) * 3 + (((32, 512), F32),),
                 id="flash-biased-bert"),
    # Serving: batch 16, 640 cache slots, 16 query / 4 KV heads of 128.
    pytest.param(_decode,
                 (((16, 1, 16, 128), BF16), ((16, 4, 640, 128), BF16),
                  ((16, 4, 640, 128), BF16), ((), I32)),
                 id="decode-serving"),
    # A small dense-ish MoE: 4 x 2048 tokens top-2 over E4, D2048 F4096.
    pytest.param(_gmm,
                 (((16384, 2048), BF16), ((4, 2048, 4096), BF16),
                  ((4,), I32)),
                 id="megablox-gmm-moe-bench"),
    # OLMoE-1B-7B's expert layer at the chip cell's size: 8192 tokens x
    # 8 choices into 64 experts; gate/up [2048 -> 1024] and down
    # [1024 -> 2048] straddle the 1024 tile, so each backward direction
    # runs on its own clamp (ops/grouped_moe.py:_bwd_tilings).
    pytest.param(_gmm,
                 (((65536, 2048), BF16), ((64, 2048, 1024), BF16),
                  ((64,), I32)),
                 id="megablox-gmm-olmoe-gate-up"),
    pytest.param(_gmm,
                 (((65536, 1024), BF16), ((64, 1024, 2048), BF16),
                  ((64,), I32)),
                 id="megablox-gmm-olmoe-down"),
    # The same out of OLMoE's stack of two layers (PR 33).
    pytest.param(_gmm_layer_1_of_2,
                 (((65536, 2048), BF16), ((2, 64, 2048, 1024), BF16),
                  ((64,), I32)),
                 id="megablox-gmm-olmoe-gate-up-layer-of-stack"),
    pytest.param(_gmm_layer_1_of_2,
                 (((65536, 1024), BF16), ((2, 64, 1024, 2048), BF16),
                  ((64,), I32)),
                 id="megablox-gmm-olmoe-down-layer-of-stack"),
    # Trinity-Mini's share: one chunk of 32,768 sorted slots into the 16
    # experts held (the groups cover the held rows, about half of it).
    pytest.param(_gmm,
                 (((32768, 2048), BF16), ((16, 2048, 1024), BF16),
                  ((16,), I32)),
                 id="megablox-gmm-trinitymini-share-gate-up"),
    pytest.param(_gmm,
                 (((32768, 1024), BF16), ((16, 1024, 2048), BF16),
                  ((16,), I32)),
                 id="megablox-gmm-trinitymini-share-down"),
    # LFM2-8B-A1B's attention at the chip cell's size: heads 64 wide,
    # 32 on 8 (no head under 128 had run through the training kernels).
    pytest.param(_flash_fwd_bwd,
                 (((2, 8192, 32, 64), BF16),)
                 + (((2, 8192, 8, 64), BF16),) * 2,
                 id="flash-fwd+bwd-lfm2moe-b2s8192"),
    # Its share: one chunk of 32,768 sorted slots into the 8 experts
    # held, expert width 1792 = 1.75 of the 1024 tile.
    pytest.param(_gmm,
                 (((32768, 2048), BF16), ((8, 2048, 1792), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-lfm2moe-share-gate-up"),
    pytest.param(_gmm,
                 (((32768, 1792), BF16), ((8, 1792, 2048), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-lfm2moe-share-down"),
    # Qwen3-Next-80B-A3B's full attention at the chip cell's size: heads
    # 256 wide, 16 on 2 (no head over 128 and no group of 8 had run
    # through the training kernels).
    pytest.param(_flash_fwd_bwd,
                 (((2, 8192, 16, 256), BF16),)
                 + (((2, 8192, 2, 256), BF16),) * 2,
                 id="flash-fwd+bwd-qwen3next-b2s8192"),
    # Its share: one chunk of 20,480 sorted slots into the 32 experts
    # held, expert width 512 = half of the 1024 tile.
    pytest.param(_gmm,
                 (((20480, 2048), BF16), ((32, 2048, 512), BF16),
                  ((32,), I32)),
                 id="megablox-gmm-qwen3next-share-gate-up"),
    pytest.param(_gmm,
                 (((20480, 512), BF16), ((32, 512, 2048), BF16),
                  ((32,), I32)),
                 id="megablox-gmm-qwen3next-share-down"),
    # Nemotron-3-Super's attention at the chip cell's size: 32 heads on
    # 2 (no group of 16 had run through the training kernels).
    pytest.param(_flash_fwd_bwd,
                 (((1, 8192, 32, 128), BF16),)
                 + (((1, 8192, 2, 128), BF16),) * 2,
                 id="flash-fwd+bwd-nemotron3super-b1s8192"),
    # Its share: one chunk of 5,632 sorted slots (no multiple of the
    # gathers' 2048-row block) into the 8 experts held, rows as wide as
    # the latent space, expert width 2688 = 21 x 128: two matrices.
    pytest.param(_gmm,
                 (((5632, 1024), BF16), ((8, 1024, 2688), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-nemotron3super-share-up"),
    pytest.param(_gmm,
                 (((5632, 2688), BF16), ((8, 2688, 1024), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-nemotron3super-share-down"),
    # Xing4.0's latent attention at the chip cell's size: 32 heads whose
    # queries and keys are 192 wide (one and a half lane tiles: no such
    # width had run through the kernels) beside values 128 wide.
    pytest.param(_flash_latent_fwd_bwd,
                 (((1, 8192, 32, 192), BF16),) * 2
                 + (((1, 8192, 32, 128), BF16),),
                 id="flash-fwd+bwd-xing4-b1s8192-192-128"),
    # Its share: 32,768 sorted slots into the 8 experts held, width 1024.
    pytest.param(_gmm,
                 (((32768, 3584), BF16), ((8, 3584, 1024), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-xing4-share-gate-up"),
    pytest.param(_gmm,
                 (((32768, 1024), BF16), ((8, 1024, 3584), BF16),
                  ((8,), I32)),
                 id="megablox-gmm-xing4-share-down"),
])
def test_kernel_compiles_for_described_v5e(for_tpu, fn, shapes):
    assert "tpu_custom_call" in for_tpu(fn, *shapes)


# sha256 of the StableHLO text (the serialized Mosaic kernels in it) that
# a flash call with ONE width for q, k and v and no scale lowers to for
# the described v5e, at a small shape, as the commit before latent
# attention's widths and scale gave it (38c2b3c, with
# ``jax_traceback_in_locations_limit`` 0 as ``enable_compile_cache``
# sets it: a kernel is serialized with its locations): the cells that
# ran before lower to the text they lowered to.
_FLASH_TEXT_BEFORE = {
    "plain": "6a872146a9794b17f0adb79dcad6d27f7c334a3bb733d764087b9b20"
             "61c72607",
    "window": "2af7471246f09261b7dc8bef2fe606e5186269b2839cfbc64a9ab035"
              "0ee16364"}


@pytest.mark.parametrize("kind, shapes, kw", [
    ("plain", (((2, 512, 4, 128), BF16), ((2, 512, 2, 128), BF16),
               ((2, 512, 2, 128), BF16)), {}),
    ("window", (((1, 2048, 4, 128), BF16), ((1, 2048, 2, 128), BF16),
                ((1, 2048, 2, 128), BF16)), {"window": 512})],
    ids=["plain", "window"])
def test_a_flash_call_of_one_width_lowers_to_the_text_it_did(
        for_tpu, v5e_chip, kind, shapes, kw):
    import hashlib

    from horovod_tpu.ops import flash_attention

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, **kw).astype(F32) * w.astype(F32)),
            argnums=(0, 1, 2))(q, k, v)

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        args = [jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)
                for s, d in shapes + (shapes[0],)]
        text = jax.jit(grads).lower(*args).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _FLASH_TEXT_BEFORE[kind]


# An f32 array one wide under the default tiling: every value fills a
# whole 128-lane tile, 128 times its logical size in HBM.
_LANE_PADDED = re.compile(r"f32\[[\d,]+,1\]\{[^}]*T\(8,128\)[^}]*\}")


@pytest.mark.parametrize("fn,shapes,statistic", [
    pytest.param(_flash_fwd_bwd,
                 (((2, 4096, 32, 128), BF16),)
                 + (((2, 4096, 8, 128), BF16),) * 2,
                 "f32[2,32,1,4096]", id="mistral7b-b2s4096"),
    pytest.param(_flash_fwd_bwd, (((2, 4096, 16, 128), BF16),) * 3,
                 "f32[2,16,1,4096]", id="olmoe1b7b-b2s4096"),
    pytest.param(_flash_fwd_bwd, (_TQ, _TKV, _TKV),
                 "f32[2,32,1,8192]", id="trinitymini-b2s8192-full"),
    pytest.param(_flash_window_fwd_bwd, (_TQ, _TKV, _TKV),
                 "f32[2,32,1,8192]", id="trinitymini-b2s8192-window2048"),
    pytest.param(_flash_fwd_bwd,
                 (((2, 8192, 32, 64), BF16),)
                 + (((2, 8192, 8, 64), BF16),) * 2,
                 "f32[2,32,1,8192]", id="lfm2moe-b2s8192-heads64"),
    pytest.param(_flash_fwd_bwd,
                 (((2, 8192, 16, 256), BF16),)
                 + (((2, 8192, 2, 256), BF16),) * 2,
                 "f32[2,16,1,8192]", id="qwen3next-b2s8192-heads256"),
    pytest.param(_flash_chunk,
                 (((1, 16, 2048, 128), BF16), ((1, 4, 2048, 128), BF16),
                  ((1, 4, 2048, 128), BF16), ((), I32), ((), I32)),
                 "f32[1,16,1,2048]", id="chunk-offsets-lse-cotangent"),
])
def test_flash_row_statistics_cross_hbm_lane_dense(for_tpu, fn, shapes,
                                                   statistic):
    """Forward + backward at the attention shapes the cells run, as the
    chip's compiler emits them (PR 37): ``lse`` and ``delta`` cross the
    Mosaic calls' boundary as ``f32[B,H,1,T]``, T on the lanes. Nothing
    in the program, no operand or result of a ``tpu_custom_call`` and no
    ``copy``, is an f32 array one wide under ``T(8,128)``: as
    ``f32[B,H,T,1]`` each statistic was 268 MB at B2 H32 T8192 where 2 MB
    are meant, and a ``copy`` of ``delta`` into that form stood in front
    of every backward call."""
    text = for_tpu(fn, *shapes)
    assert text.count("tpu_custom_call") >= 2
    assert not _LANE_PADDED.findall(text)
    assert statistic in text


def _delta_rule_fwd_bwd(q, k, v, g, beta, key_heads=None):
    from horovod_tpu.ops.gated_delta_rule import gated_delta_rule

    return jax.grad(lambda *a: gated_delta_rule(
        *a, key_heads=key_heads).astype(F32).sum(),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)


# token-major, as the chain round the rule leaves and takes them: 16 key
# heads and 32 value heads of 128
_RULE_KEYS, _RULE_VALUES = ((2, 8192, 2048), BF16), ((2, 8192, 4096), BF16)
_RULE_HEAD, _RULE_GATE = ((2, 8192, 32, 128), BF16), ((2, 8192, 32), F32)
_RULE_KERNELS = ("hvd_gdn_rule_fwd", "hvd_gdn_rule_bwd")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)")


def _entry_instructions(text):
    """({name: (result types, opcode, operand names)} of a compiled
    program's ENTRY computation, the layouts cut off, {a Mosaic call's
    ``kernel_metadata`` name: its instruction})."""
    found, kernels, name = {}, {}, None
    for line in text[text.index("\nENTRY "):].splitlines():
        m = _INSTRUCTION.match(re.sub(r"\{[^{}]*\}", "", line))
        if m:
            name, types, opcode, operands = m.groups()
            found[name] = (re.findall(r"\w+\[[\d,]*\]", types), opcode,
                           re.findall(r"%([\w.\-]+)", operands))
        kernel = re.search(r'"kernel":"(\w+)"', line)
        # on a line of its own inside the instruction (a
        # get-tuple-element of the call's carries the name too)
        if kernel and found[name][1] == "custom-call":
            kernels[kernel.group(1)] = name
    return found, kernels


def test_gated_delta_rule_compiles_for_described_v5e(for_tpu):
    """Qwen3-Next's delta rule at the chip cell's size (B2 T8192, 32
    value heads of 128 on 16 key heads), forward and backward, as the
    chip's compiler takes it: everything downstream of the inverse is
    the kernel pair, each by the name a device trace shows
    (``kernel_metadata``), and no ``while`` over chunks is left; the
    states kept are the 128 CHUNKS' ([128, 2, 32, 128, 128] float32), no
    operand is token-major by heads (a scan over the 8192 tokens would
    slice one), and the triangular systems are 64 wide.

    What PR 49 is for: the kernels take ``q``, ``k``, ``v``, the two
    gates and the inverse, and no factor; ``qg``, ``u``, ``w``, ``kd``
    (134 MB each) and their cotangents never cross HBM. What PR 62 is
    for: they take ``q``, ``k``, ``v`` and ``do`` and leave ``o``,
    ``dq``, ``dk``, ``dv`` TOKEN-MAJOR, as the program's arguments and
    results lie (``q`` and ``k`` at their 16 key heads): no array of a
    chunked value head's shape is left anywhere, and of a chunked key
    head's only ``k`` on its way into ``K K^T`` and that product's share
    of ``dk`` on its way back."""
    text = for_tpu(functools.partial(_delta_rule_fwd_bwd, key_heads=16),
                   _RULE_KEYS, _RULE_KEYS, _RULE_VALUES, _RULE_GATE,
                   _RULE_GATE)
    for name in _RULE_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert " while(" not in text
    assert "f32[128,2,32,128,128]" in text        # the states kept
    assert "[8192,2,32," not in text              # no token-major scan
    assert re.search(r"f32\[2,128,(32|16,2),(1,)?64,64\]", text)  # (I + A)^-1

    keys, values = "bf16[2,8192,2048]", "bf16[2,8192,4096]"
    square, gate = "bf16[2,128,32,64,64]", "f32[2,32,128,64]"
    states = "f32[128,2,32,128,128]"
    entry, kernels = _entry_instructions(text)
    calls = {kernel: (entry[call][0],
                      [entry[x][0][0] for x in entry[call][2]])
             for kernel, call in kernels.items()}
    raw = [keys, keys, values, gate, gate, square]  # q k v gamma beta inv
    assert calls["hvd_gdn_rule_fwd"] == ([values, states], raw)
    assert calls["hvd_gdn_rule_bwd"] == (
        [keys, keys, values, square, gate, gate], raw + [states, values])
    assert "[2,128,32,64,128]" not in text        # a chunked value head's
    chunked = [name for name, (types, opcode, _) in entry.items()
               if types == ["bf16[2,128,16,64,128]"] and opcode not in (
                   "get-tuple-element", "bitcast", "parameter")]
    assert len(chunked) <= 3, chunked


def test_the_rule_by_heads_compiles_for_described_v5e(for_tpu):
    """Operands by heads, ``[B, T, H, d]`` (the benchmark's comparison
    with the recurrence hands them over so): read as token-major, the
    same kernel pair on the same blocks."""
    text = for_tpu(_delta_rule_fwd_bwd, _RULE_HEAD, _RULE_HEAD, _RULE_HEAD,
                   _RULE_GATE, _RULE_GATE)
    entry, kernels = _entry_instructions(text)
    wide = "bf16[2,8192,4096]"
    assert entry[kernels["hvd_gdn_rule_fwd"]][0][0] == wide
    assert entry[kernels["hvd_gdn_rule_bwd"]][0][:3] == [wide] * 3


def test_the_rule_at_two_widths_compiles_for_described_v5e(for_tpu):
    """Olmo-Hybrid's delta rule at the chip cell's size (B2 T8192, 30
    heads, keys 96 and values 192 wide: neither a lane tile), forward
    and backward, token-major as the chain leaves its operands: 30
    heads of 96 have no step of whole lane tiles, so the kernel pair by
    name takes the WHOLE width a step (a head a lane window wherever it
    falls, the VMEM asked for by name: unasked, the chip's compiler
    refuses the forward at 21 MiB of 16), the states kept the 128
    chunks' at 96 x 192, no scan over tokens and no chunk-major copy of
    an operand."""
    key, value = ((2, 8192, 30 * 96), BF16), ((2, 8192, 30 * 192), BF16)
    gate = ((2, 8192, 30), F32)
    text = for_tpu(functools.partial(_delta_rule_fwd_bwd, key_heads=30),
                   key, key, value, gate, gate)
    for name in _RULE_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert " while(" not in text
    assert "f32[128,2,30,96,192]" in text         # the states kept
    assert "[8192,2,30," not in text              # no token-major scan
    entry, kernels = _entry_instructions(text)
    assert entry[kernels["hvd_gdn_rule_fwd"]][0][0] == "bf16[2,8192,5760]"
    assert entry[kernels["hvd_gdn_rule_bwd"]][0][:3] == [
        "bf16[2,8192,2880]", "bf16[2,8192,2880]", "bf16[2,8192,5760]"]
    assert "[2,128,30,64,192]" not in text        # a chunked value head's


def test_three_layers_of_the_rule_lower_each_kernel_once(for_tpu, v5e_chip):
    """The set-up budget's guard (PERF.md section 6, PR 44). A
    ``pallas_call`` is lowered to Mosaic at every site of every trace,
    compile cache or not; behind ONE jitted wrapper the three layers'
    forward, forward again under remat, and backward lower to one
    private function a kernel form that every site calls: the backward
    once, the forward twice (keeping the states, and not). Three still,
    now that the pair forms the WY factors too (PR 49): the backward's
    factor half is no kernel of its own."""
    from horovod_tpu.ops.gated_delta_rule import gated_delta_rule

    def loss(q, k, v, g, beta):
        for _ in range(3):
            v = jax.checkpoint(functools.partial(
                gated_delta_rule, key_heads=16))(q, k, v, g, beta)
        return v.astype(F32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)
            for s, d in (_RULE_KEYS,) * 2 + (_RULE_VALUES,)
            + (_RULE_GATE,) * 2]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).as_text()
    assert [text.count(name) for name in _RULE_KERNELS] == [2, 1]
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @_kernel_fwd") >= 5   # the sites are calls
    assert text.count("call @_kernel_bwd") == 3


def _selective_scan_fwd_bwd(u, dt, A, Bm, Cm, D):
    from horovod_tpu.ops.selective_scan import selective_scan

    return jax.grad(lambda *a: selective_scan(*a).astype(F32).sum(),
                    argnums=(0, 1, 2, 3, 4, 5))(u, dt, A, Bm, Cm, D)


# Jamba2-3B's mamba mixer at the chip cell's size: B1 T8192, 5120
# channels of 16 states.
_SCAN = (((1, 8192, 5120), BF16), ((1, 8192, 5120), F32),
         ((5120, 16), F32), ((1, 8192, 16), BF16), ((1, 8192, 16), BF16),
         ((5120,), F32))
_SCAN_KERNELS = ("hvd_ssm_scan_fwd", "hvd_ssm_scan_bwd")


def test_selective_scan_compiles_for_described_v5e(for_tpu):
    """Jamba2's selective scan at the chip cell's size, forward and
    backward, as the chip's compiler takes it: the kernel pair, each by
    the name a device trace shows (``kernel_metadata``), and no
    ``while`` over tokens or chunks is left; the states kept are the 64
    CHUNKS' ([1, 64, 16, 5120] float32), and nothing the size of the
    scan materialised ([8192, 5120, 16] in any order) exists."""
    text = for_tpu(_selective_scan_fwd_bwd, *_SCAN)
    for name in _SCAN_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert " while(" not in text
    assert "f32[1,64,16,5120]" in text            # the states kept
    assert not re.search(r"\[(1,)?(8192,5120,16|8192,16,5120|"
                         r"5120,16,8192|16,5120,8192)\]", text)


def test_thirteen_layers_of_the_scan_lower_each_kernel_once(v5e_chip,
                                                           for_tpu):
    """The set-up budget's guard (PERF.md section 6, PR 44's mechanism):
    behind ONE jitted wrapper the thirteen layers' forward, forward
    again under remat, and backward lower to one private function a
    kernel form that every site calls: the backward once, the forward
    twice (keeping the chunks' states, and not)."""
    from horovod_tpu.ops.selective_scan import selective_scan

    def loss(u, dt, A, Bm, Cm, D):
        for _ in range(13):
            u = jax.checkpoint(selective_scan)(u, dt, A, Bm, Cm, D)
        return u.astype(F32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)
            for s, d in _SCAN]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        *args).as_text()
    assert [text.count(name) for name in _SCAN_KERNELS] == [2, 1]
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @_kernel_fwd") >= 25  # the sites are calls
    assert text.count("call @_kernel_bwd") == 13


def _ssd_fwd_bwd(x, dt, A, Bm, Cm, D):
    from horovod_tpu.ops.ssd import ssd

    return jax.grad(lambda *a: ssd(*a).astype(F32).sum(),
                    argnums=(0, 1, 2, 3, 4, 5))(x, dt, A, Bm, Cm, D)


# Nemotron-3-Super's mamba2 mixer at the chip cell's size: B1 T8192, 128
# heads of 64 channels, 128 states, B / C by 8 groups of 16 heads.
_SSD = (((1, 8192, 128, 64), BF16), ((1, 8192, 128), F32), ((128,), F32),
        ((1, 8192, 8, 128), BF16), ((1, 8192, 8, 128), BF16),
        ((128,), F32))
_SSD_KERNELS = ("hvd_ssd_fwd", "hvd_ssd_bwd")


def test_ssd_compiles_for_described_v5e(for_tpu):
    """The SSD recurrence at the chip cell's size, forward and backward,
    as the chip's compiler takes it: the kernel pair, each by the name a
    device trace shows (``kernel_metadata``), and no ``while`` over
    tokens or chunks is left; the states kept are the 64 CHUNKS' ([64, 1,
    8192, 128] float32), and nothing the size of the recurrence
    materialised ([8192, 128, 64, 128] in any order) exists."""
    text = for_tpu(_ssd_fwd_bwd, *_SSD)
    for name in _SSD_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert " while(" not in text
    assert "f32[64,1,8192,128]" in text           # the states kept
    assert not re.search(r"\[(1,)?(8192,128,64,128|8192,8192,128|"
                         r"128,64,128,8192)\]", text)


def test_five_layers_of_the_ssd_lower_each_kernel_once(v5e_chip, for_tpu):
    """The set-up budget's guard (PERF.md section 6, PR 44's mechanism):
    behind ONE jitted wrapper the five layers' forward, forward again
    under remat, and backward lower to one private function a kernel
    form that every site calls: the backward once, the forward twice
    (keeping the chunks' states, and not): no more than one layer
    does."""
    from horovod_tpu.ops.ssd import ssd

    def loss(x, dt, A, Bm, Cm, D):
        for _ in range(5):
            x = jax.checkpoint(ssd)(x, dt, A, Bm, Cm, D)
        return x.astype(F32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e_chip) for s, d in _SSD]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        *args).as_text()
    assert [text.count(name) for name in _SSD_KERNELS] == [2, 1]
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @_kernel_fwd") >= 9   # the sites are calls
    assert text.count("call @_kernel_bwd") == 5


# MiniCPM-SALA's two mixers at the chip cell's size: B1 T32768, the
# sparse layer's 32 query heads on 2 key/value heads of 128 with its
# table (a word a tile of 16 tokens and block of 64 keys), the lightning
# layer's 32 heads of 128 x 128 states, a group a head.
_SPARSE_KERNELS = ("hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd")
_SALA_T = 32768
_SPARSE = (((1, _SALA_T, 32, 128), BF16), ((1, _SALA_T, 2, 128), BF16),
           ((1, _SALA_T, 2, 128), BF16),
           ((1, 2, _SALA_T // 16, _SALA_T // 64), I32))
_LIGHTNING = (((1, _SALA_T, 32, 128), BF16),) * 3
_SELECTION = dict(block=64, topk=64, kernel=32, stride=16, init_blocks=1,
                  window_blocks=32)


def _sparse_fwd_bwd(q, k, v, table):
    from horovod_tpu.ops.sparse_attention import sparse_attention

    return jax.value_and_grad(
        lambda q, k, v: sparse_attention(q, k, v, table, 64).astype(
            F32).sum(), argnums=(0, 1, 2))(q, k, v)


def _select(q, k):
    from horovod_tpu.ops.sparse_attention import select_blocks

    return select_blocks(q, k, **_SELECTION)


def _lightning_fwd_bwd(q, k, v):
    from horovod_tpu.ops.ssd import ssd

    rates = -jnp.exp2(-8.0 * (jnp.arange(32, dtype=F32) + 1.0) / 32)
    return jax.value_and_grad(
        lambda q, k, v: ssd(v, jnp.ones(v.shape[:3], F32), rates, k, q,
                            None, 128, "hvd.lightning.core").astype(
            F32).sum(), argnums=(0, 1, 2))(q, k, v)


def test_sparse_attention_compiles_for_described_v5e(for_tpu):
    """The sparse pair at the chip cell's size as the chip's compiler
    takes it: a group's keys and values whole in VMEM (the backward their
    float32 gradients too), the table's row in SMEM, each kernel by the
    name a device trace shows; no [T, T] plane and no dense mask exists;
    the forward walks its list with loops alone (no conditional is left
    in its body; the backward's two are its group's first and last
    tile); and the selection beside it compiles with no kernel at all
    and no sort (its 64 of 512 blocks are a threshold found by
    counting)."""
    text = for_tpu(_sparse_fwd_bwd, *_SPARSE)
    for name in _SPARSE_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    # a kernel's body travels as MLIR bytecode, its operations' names in
    # the clear
    ops = {name: base64.b64decode(re.search(
        r'"body":"([^"]+)"', text[text.index(f'"kernel":"{name}"'):])[1])
        for name in _SPARSE_KERNELS}
    assert all(b"scf.for" in body for body in ops.values())
    assert b"scf.if" not in ops["hvd_sparse_attn_fwd"]
    assert b"scf.if" in ops["hvd_sparse_attn_bwd"]
    assert not re.search(r"\[(1,)?(2,|32,)?32768,(2,|16,|32,)*32768\]", text)
    # the row statistics cross HBM lane-dense, a tile's a row
    assert "f32[1,2,2048,1,256]" in text
    text = for_tpu(_select, *_SPARSE[:2])
    assert "tpu_custom_call" not in text
    assert not re.search(r"\bsort\b", text)
    assert "s32[1,2,2048,512]" in text                # the table
    assert not re.search(r"\[(1,)?32768,(2,)?(16,)?2047\]", text)


def test_the_ssd_pair_takes_a_head_a_group_for_described_v5e(for_tpu):
    """Lightning Attention's shapes through ``ops/ssd.py``: 32 heads of
    128 channels x 128 states, a group a head, ``dt`` = 1, no ``D``: a
    step holds one head, ``dB`` / ``dC`` leave in bf16 (no float32 [T,
    32 x 128] parts), the states kept are the 256 chunks'."""
    text = for_tpu(_lightning_fwd_bwd, *_LIGHTNING)
    for name in _SSD_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    assert " while(" not in text
    assert "f32[256,1,4096,128]" in text          # the states kept
    # what the backward kernel returns: dx, dB, dC in bf16, the gates'
    # gradients lane-dense in float32, and no float32 [T, 32 x 128] part
    at = text.index('"kernel":"hvd_ssd_bwd"')
    head = text[text.rfind(" = (", 0, at):at]
    result = head[:head.index("custom-call(")]
    assert result.count("bf16[1,32768,4096]") == 3, result
    assert "f32[1,32768,4096]" not in result


def test_four_layers_of_sala_lower_each_kernel_once_a_direction(v5e_chip,
                                                                for_tpu):
    """A whole published period (a sparse layer, three lightning layers
    under the layer scan) at the cell's sequence and a narrow model: the
    grad program lowers the sparse pair once each and the SSD pair once
    a direction (the forward twice: keeping the chunks' states, and
    not), whatever the depth."""
    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

    cfg = LlamaConfig(
        vocab_size=512, d_model=256, n_layers=4, n_heads=32, n_kv_heads=2,
        d_head=128, d_ff=512, norm_eps=1e-6, rope_theta=10000.0,
        layer_types=("sparse_attention",) + ("lightning_attention",) * 3,
        qk_norm="head", attn_gate=True, embed_mult=12.0,
        residual_mult=1.4 / 32 ** 0.5, logit_div=16.0, lightning_heads=32,
        lightning_head_dim=128, lightning_chunk=128, lightning_depth=32,
        sparse_block=64, sparse_topk=64, sparse_kernel=32, sparse_stride=16,
        sparse_init_blocks=1, sparse_window_blocks=32,
        sparse_dense_len=8192, ffn_chunk=2048, loss_chunk=2048,
        dtype="bfloat16", param_dtype="bfloat16", remat="attn")
    shapes = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e_chip), shapes)
    batch = {k: jax.ShapeDtypeStruct((1, _SALA_T), I32, sharding=v5e_chip)
             for k in ("tokens", "targets")}
    text = jax.jit(jax.grad(lambda p, b: llama_loss(p, b, cfg))).lower(
        params, batch).as_text()
    assert [text.count(name) for name in _SPARSE_KERNELS] == [1, 1]
    assert [text.count(name) for name in _SSD_KERNELS] == [2, 1]
    assert "hvd_flash" not in text      # past the dense length: no flash
    # a sequence at the dense length runs the flash kernels instead
    short = {k: jax.ShapeDtypeStruct((1, 8192), I32, sharding=v5e_chip)
             for k in batch}
    text = jax.jit(jax.grad(lambda p, b: llama_loss(p, b, cfg))).lower(
        params, short).as_text()
    assert "hvd_flash_fwd" in text and "hvd_sparse_attn" not in text


@pytest.mark.slow
def test_the_sala_cells_grad_program_fits_the_described_v5e(v5e_chip,
                                                            for_tpu):
    """The cell's grad program at [1, 32768] with the file's ``remat``,
    ``ffn_chunk``, ``loss_chunk`` and chunk of the recurrence compiles
    for the described v5e within the 15.75 GiB its programs get, Adam's
    two moments beside it, and to the peak the file records. Two minutes
    and more of one core: not in tier-1, whose clock has 100 s to spare
    (CHANGES.md, PR 55); every run of the cell on the chip proves the
    fit again."""
    sys.path.insert(0, REPO)
    from chipbench import child

    _, _, config, traffic = child.find_cell("minicpmsala.spmd.b1s32768")
    model = child.load_file("models", "minicpmsala").Model(config, traffic)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e_chip), shapes)
    batch = {k: jax.ShapeDtypeStruct((1, _SALA_T), I32, sharding=v5e_chip)
             for k in ("tokens", "targets")}
    compiled = jax.jit(
        lambda p, d: jax.value_and_grad(
            lambda p, d: model.loss(p, (), d)[0])(p, d),
        compiler_options=model.compiler_options).lower(
        params, batch).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    moments = 2 * sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(shapes))
    assert peak + moments < 15.75 * 2 ** 30
    assert abs(peak / 1e9 - config["assumed"]["compiled_peak_gb"]) < 0.2


@pytest.mark.slow
def test_the_xing4_cells_grad_program_fits_the_described_v5e(v5e_chip,
                                                             for_tpu):
    """The cell's grad program at [1, 8192] with the file's ``remat`` and
    ``loss_chunk`` compiles for the described v5e within the 15.75 GiB
    its programs get, Adam's two moments beside it, and holds the flash
    pair, latent attention's seam (``ops/mla_prep.py``) and the
    hyper-connections' two pairs (``ops/hc_mix.py``) by name and no other
    named Mosaic call (``moe_gmm_ms_per_step`` takes every
    call without ``hvd_flash`` in its name for megablox's). Five
    minutes of one core: not in tier-1 (CHANGES.md, PR 57); every run of
    the cell on the chip proves the fit again."""
    sys.path.insert(0, REPO)
    from chipbench import child

    _, _, config, traffic = child.find_cell("xing4.spmd.b1s8192")
    model = child.load_file("models", "xing4").Model(config, traffic)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e_chip), shapes)
    batch = {k: jax.ShapeDtypeStruct((1, 8192), I32, sharding=v5e_chip)
             for k in ("tokens", "targets")}
    compiled = jax.jit(
        lambda p, d: jax.value_and_grad(
            lambda p, d: model.loss(p, (), d)[0])(p, d),
        compiler_options=model.compiler_options).lower(
        params, batch).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    moments = 2 * sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(shapes))
    assert peak + moments < 15.75 * 2 ** 30
    named = set(re.findall(r'"kernel":"([a-z_0-9]+)"', compiled.as_text()))
    assert named == {"hvd_flash_fwd", "hvd_flash_bwd_fused",
                     "hvd_mla_prep_fwd", "hvd_mla_prep_bwd", *_HC_KERNELS}


_CHAIN_KERNELS = ("hvd_gdn_chain_in_fwd", "hvd_gdn_chain_in_bwd",
                  "hvd_gdn_chain_out_fwd", "hvd_gdn_chain_out_bwd")


@pytest.mark.slow
def test_the_olmohybrid_cells_grad_program_fits_the_described_v5e(
        v5e_chip, for_tpu):
    """The cell's grad program at [2, 8192] with the file's ``remat``
    and ``loss_chunk`` compiles for the described v5e within the 15.75
    GiB its programs get, Adam's two moments AND a second set of
    gradients beside it (the step runs one ahead), and holds the flash
    pair, the rule's pair and the chain's two pairs by name, and no
    float32 array of the convolved columns (the chain's expression). Two
    minutes of one core: not in tier-1; every run of the cell on the
    chip proves the fit again (PR 61: 9.29 GiB, 8.70 of them
    temporaries, beside 3.46 of moments)."""
    sys.path.insert(0, REPO)
    from chipbench import child

    _, _, config, traffic = child.find_cell("olmohybrid.spmd.b2s8192")
    model = child.load_file("models", "olmohybrid").Model(config, traffic)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e_chip), shapes)
    batch = {k: jax.ShapeDtypeStruct((2, 8192), I32, sharding=v5e_chip)
             for k in ("tokens", "targets")}
    lowered = jax.jit(
        lambda p, d: jax.value_and_grad(
            lambda p, d: model.loss(p, (), d)[0])(p, d),
        compiler_options=model.compiler_options).lower(params, batch)
    assert model.check_lowering(lowered.as_text(), True) is None
    compiled = lowered.compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    state = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert peak + 3 * state < 15.75 * 2 ** 30
    text = compiled.as_text()
    named = set(re.findall(r'"kernel":"([a-z_0-9]+)"', text))
    assert named == {"hvd_flash_fwd", "hvd_flash_bwd_fused",
                     "hvd_gdn_rule_fwd", "hvd_gdn_rule_bwd",
                     *_CHAIN_KERNELS}
    assert "f32[2,8192,11520]" not in text
def test_the_ouro_cells_grad_program_fits_the_described_v5e(v5e_chip,
                                                            for_tpu):
    """The looped cell's grad program at [2, 4096], four trips of twelve
    layers under the file's ``remat`` and ``loss_chunk``, lowers with the
    four exits side by side and compiles for the described v5e: the
    flash pair and the q/k seam's pair by name, the sum of the shared leaves' gradients over
    the trips under ``hvd.loop`` reading all four at once, and its
    temporaries under 9.5 GB (PR 64 read 9,162,386,432 B, a peak of
    7.99 GB with the parameters and the gradients it hands back, beside
    2.67 GB of moments and a second set of gradients: the step runs one
    ahead). A form of the loop that kept more alive (the trips as a
    ``lax.scan``: 9.76 GB of temporaries, a peak of 9.44) fails the
    bound. A minute of one core: the one whole-program compile of
    tier-1; every run of the cell on the chip proves the fit again."""
    sys.path.insert(0, REPO)
    from chipbench import child

    _, _, config, traffic = child.find_cell("ouro.spmd.b2s4096")
    model = child.load_file("models", "ouro").Model(config, traffic)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e_chip), shapes)
    batch = {k: jax.ShapeDtypeStruct((2, 4096), I32, sharding=v5e_chip)
             for k in ("tokens", "targets")}
    lowered = jax.jit(
        lambda p, d: jax.value_and_grad(
            lambda p, d: model.loss(p, (), d)[0])(p, d),
        compiler_options=model.compiler_options).lower(params, batch)
    assert model.check_lowering(lowered.as_text(), True) is None
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    state = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert state == 2 * 666_996_737
    assert memory.temp_size_in_bytes < 9.5e9
    assert memory.peak_memory_in_bytes + 3 * state < 15.75 * 2 ** 30
    text = compiled.as_text()
    assert set(re.findall(r'"kernel":"([a-z_0-9]+)"', text)) == {
        "hvd_flash_fwd", "hvd_flash_bwd_fused", "hvd_qk_prep_fwd",
        "hvd_qk_prep_bwd"}
    sums = [line for line in text.splitlines()
            if " = bf16[12,2048,5632]" in line and "fusion(" in line
            and "hvd.loop" in line]
    assert sums and all(line.count("%while") == 4 for line in sums), sums


# Qwen3-Next's linear mixer at the chip cell's size: B2 T8192, 16 key
# heads serving 32 value heads, all 128 wide, four taps.
_CHAIN = (((2, 8192, 96 * 128), BF16), ((4, 64 * 128), BF16),
          ((128,), BF16))


# Olmo-Hybrid's linear mixer at the chip cell's size: B2 T8192, 30 key
# heads of 96 and 30 value heads of 192 (the strip), four taps.
_CHAIN_TWO = (((2, 8192, 17280), BF16), ((4, 11520), BF16), ((192,), BF16))


def _chain_two_widths_fwd_bwd(qkvz, taps, gain):
    """As ``_chain_fwd_bwd``, keys 96 and values 192 wide."""
    from horovod_tpu.ops import gdn_chain

    def loss(qkvz, taps, gain):
        q, k, v, z = gdn_chain.chain_in(qkvz, taps, 30, 30, 96, 192)
        o = v * jnp.tile(q * k, (1, 1, 2))
        return gdn_chain.chain_out(o, z, gain, 1e-6).astype(F32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(qkvz, taps, gain)


def _chain_fwd_bwd(qkvz, taps, gain):
    """The chain round a stand-in for the rule (``v`` scaled by ``q
    k``: every output is read), values and gradients."""
    from horovod_tpu.ops import gdn_chain

    def loss(qkvz, taps, gain):
        q, k, v, z = gdn_chain.chain_in(qkvz, taps, 16, 32)
        o = v * jnp.tile(q * k, (1, 1, 2))
        return gdn_chain.chain_out(o, z, gain, 1e-6).astype(F32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(qkvz, taps, gain)


_SSD_CHAIN_KERNELS = ("hvd_ssd_chain_in_fwd", "hvd_ssd_chain_in_bwd",
                      "hvd_ssd_chain_out_fwd", "hvd_ssd_chain_out_bwd")
# Nemotron-3-Super's mamba2 mixer at the chip cell's size: B1 T8192,
# [z 8192, X 8192, B 1024, C 1024, r 128] side by side, four taps and
# their bias, the gain of 8 groups of 1024 channels.
_SSD_CHAIN = (((1, 8192, 18560), BF16), ((4, 10240), BF16),
              ((10240,), BF16), ((8192,), BF16))


def _ssd_chain_fwd_bwd(zxr, taps, bias, gain):
    """The chain round a stand-in for the recurrence (``X`` scaled by
    the group's ``B C`` and the head's ``r``: every output is read),
    values and gradients."""
    from horovod_tpu.ops import ssd_chain

    def loss(zxr, taps, bias, gain):
        X, Bm, Cm, z, r = ssd_chain.chain_in(zxr, taps, bias, 8192, 1024)
        y = X * jnp.tile(Bm * Cm, (1, 1, 8)) * jnp.tile(r, (1, 1, 64))
        return ssd_chain.chain_out(y, z, gain, 8, 1e-5).astype(F32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
        zxr, taps, bias, gain)


_HC_KERNELS = ("hvd_hc_pre_fwd", "hvd_hc_pre_bwd", "hvd_hc_post_fwd",
               "hvd_hc_post_bwd")
# Xing4.0's four streams at the chip cell's size: B1 T8192 D3584, a
# part's three leaves (float32, as the parameters are kept), and the
# stand-in part's gain.
_HC = (((1, 4, 8192, 3584), BF16), ((4, 3584, 24), F32), ((3,), F32),
       ((24,), F32), ((3584,), BF16))


class _HcSizes:
    hc_mult, hc_sinkhorn_iters, hc_eps = 4, 20, 1e-6
    hc_clamp, norm_eps = (-30.0, 30.0), 1e-6


def _hc_fwd_bwd(X, phi, alpha, bias, gain):
    """One part round the streams on the two kernel pairs, the part a
    stand-in that reads ``u``: values and gradients."""
    from horovod_tpu.models import llama

    def loss(X, phi, alpha, bias, gain):
        lp = {"hc_a_phi": phi, "hc_a_alpha": alpha, "hc_a_bias": bias}
        out, _ = llama._hyper_connection(
            X, lp, "a", _HcSizes, lambda u: (u * gain, None))
        return (out.astype(F32) ** 2).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
        X, phi, alpha, bias, gain)


def test_hc_mix_compiles_for_described_v5e(for_tpu):
    """A part's stream mixing at the chip cell's size as the chip's
    compiler takes it (a grid step a tile of 128 tokens of all four
    streams at full width, the VMEM it asks for by name), each of the
    four kernels by the name a device trace shows, and no float32 copy
    of the carry among the results of the program's instructions (inside
    a fusion the stand-in loss squares one, in registers)."""
    text = for_tpu(_hc_fwd_bwd, *_HC)
    for name in _HC_KERNELS:
        assert f'"kernel":"{name}"' in text, name
    entry = text[text.index("\nENTRY "):]
    assert "= bf16[1,4,8192,3584]" in entry
    assert "= f32[1,4,8192,3584]" not in entry


def test_three_parts_lower_each_hc_kernel_once_a_form(v5e_chip, for_tpu):
    """The set-up budget (``test_three_mixers_...``) on the
    hyper-connections at the chip cell's size: three parts, a checkpoint
    a part as remat "attn/ffn" wraps them. A Mosaic lowering a kernel
    FORM whatever the parts, each site a call of its kernel's jitted
    wrapper: ``hvd_hc_pre_fwd`` six times as TWO lowered functions (the
    recomputation's comes with a jaxpr of its own), ``hvd_hc_post_fwd``
    three times and not six (nothing reads the recomputed ``X'``), the
    backward kernels three times each. Five lowerings a program, not
    fifteen."""
    from horovod_tpu.models import llama

    once = functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            "attn_out", "flash_o", "flash_lse"))

    def loss(X, phi, alpha, bias, gain):
        lp = {"hc_a_phi": phi, "hc_a_alpha": alpha, "hc_a_bias": bias}

        def part(X, lp):
            return llama._hyper_connection(
                X, lp, "a", _HcSizes, lambda u: (u * gain, None))[0]

        for _ in range(3):
            X = once(part)(X, lp)
        return X.astype(F32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=v5e_chip) for s, d in _HC)
    ).as_text()
    assert [text.count(name) for name in _HC_KERNELS] == [2, 1, 1, 1]
    assert text.count("tpu_custom_call") == 5
    for wrapper, sites in (("_pre_fwd", 6), ("_pre_bwd", 3),
                           ("_post_fwd", 3), ("_post_bwd", 3)):
        assert len(re.findall(rf"call @{wrapper}(_\d+)?\(", text)) \
            == sites, wrapper


@pytest.mark.parametrize("fn, shapes, names", [
    (_chain_fwd_bwd, _CHAIN, _CHAIN_KERNELS),
    (_ssd_chain_fwd_bwd, _SSD_CHAIN, _SSD_CHAIN_KERNELS),
    (_chain_two_widths_fwd_bwd, _CHAIN_TWO, _CHAIN_KERNELS)],
    ids=["gdn", "ssd", "gdn-two-widths"])
def test_chain_compiles_for_described_v5e(for_tpu, fn, shapes, names):
    """A mixer's two chain kernel pairs at its chip cell's size as the
    chip's compiler takes them (blocks of 256 tokens by 1024 lanes, the
    backward's cotangents parked where a column block has none; the SSD
    chain's last column block, ``r``'s 128 of 1024 lanes, hangs over the
    array's edge; keys 96 and values 192 wide: blocks of 256 tokens by
    1152 lanes of the strip, three lane groups of four key or two value
    heads), each by the name a device trace shows, and no float32 array
    of the convolved columns beside them."""
    text = for_tpu(fn, *shapes)
    for name in names:
        assert f'"kernel":"{name}"' in text, name
    assert "f32[2,8192,11520]" not in text


def test_three_mixers_lower_each_chain_kernel_once_a_form(v5e_chip,
                                                         for_tpu):
    """The set-up budget again (``test_three_layers_of_the_rule_...``),
    on the mixer itself at the chip cell's size, three layers, a
    checkpoint a stage with remat "attn/ffn"'s policy: a Mosaic lowering
    a kernel FORM whatever the layers, each site a call of its kernel's
    jitted wrapper. Stage one's forward runs three times (the
    recomputation has no reader for ``q``, ``k``, ``v``: its backward
    starts from ``qkvz``), stage two's six (the output projection's
    gradient reads the gated norm again), and those six are TWO lowered
    functions of one text: the recomputation's comes through the
    checkpoint's partial evaluation with a jaxpr of its own. Five
    lowerings a program, not fifteen."""
    from horovod_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=2048, n_layers=2, n_heads=16, n_kv_heads=2,
        d_head=256, d_ff=512, norm_eps=1e-6, conv_taps=4,
        layer_types=("linear_attention", "full_attention"),
        linear_key_heads=16, linear_value_heads=32, linear_key_dim=128,
        linear_value_dim=128, dtype="bfloat16", param_dtype="bfloat16",
        remat="attn/ffn")
    once = functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            "attn_out", "flash_o", "flash_lse"))
    leaves = {"gdn_norm": (2048,), "gdn_in": (2048, 96 * 128),
              "gdn_ba": (2048, 64), "gdn_conv": (4, 64 * 128),
              "gdn_a_log": (32,), "gdn_dt_bias": (32,),
              "gdn_out_norm": (128,), "gdn_out": (32 * 128, 2048)}

    def loss(x, lp):
        for _ in range(3):
            x = x + llama._gated_delta_net(x, lp, cfg, None, None, once)
        return x.astype(F32).sum()

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=v5e_chip)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        spec((2, 8192, 2048)), {k: spec(v) for k, v in leaves.items()}
    ).as_text()
    assert [text.count(name) for name in _CHAIN_KERNELS] == [1, 1, 2, 1]
    for wrapper, sites in (("_in_fwd", 3), ("_in_bwd", 3),
                           ("_out_fwd", 6), ("_out_bwd", 3)):
        assert len(re.findall(rf"call @{wrapper}(_\d+)?\(", text)) \
            == sites, wrapper


def test_five_mamba2_mixers_lower_each_chain_kernel_once_a_form(v5e_chip,
                                                               for_tpu):
    """The set-up budget on the ``mamba2`` mixer at the chip cell's
    size, five layers, a checkpoint a layer as remat "attn" wraps a
    one-part layer: a Mosaic lowering a kernel FORM whatever the layers,
    each site a call of its kernel's jitted wrapper. Both stages'
    forward run ten times (the recurrence and the output projection's
    gradient read them again) as TWO lowered functions of one text each,
    the recomputation's with a jaxpr of its own
    (``test_three_mixers_...``); the backward kernels once. Six
    lowerings a program beside the recurrence's three, and no float32
    of tokens x groups x channels."""
    from horovod_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=2,
        d_head=128, d_ff=512, norm_eps=1e-5, conv_taps=4,
        mamba_conv_bias=True, one_part_layers=True,
        layer_types=("mamba2", "full_attention"), ssd_heads=128,
        ssd_head_dim=64, ssd_state=128, ssd_groups=8, ssd_chunk=128,
        dtype="bfloat16", param_dtype="bfloat16", remat="attn")
    leaves = {"ssd_norm": (4096,), "ssd_in": (4096, 18560),
              "ssd_conv": (4, 10240), "ssd_conv_bias": (10240,),
              "ssd_a_log": (128,), "ssd_dt_bias": (128,), "ssd_d": (128,),
              "ssd_out_norm": (8192,), "ssd_out": (8192, 4096)}

    def loss(x, lp):
        for _ in range(5):
            x = x + jax.checkpoint(
                lambda x, lp: llama._mamba2(x, lp, cfg, None, None))(x, lp)
        return x.astype(F32).sum()

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=v5e_chip)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        spec((1, 8192, 4096)), {k: spec(v) for k, v in leaves.items()}
    ).as_text()
    assert [text.count(name) for name in _SSD_CHAIN_KERNELS] == [2, 1, 2, 1]
    assert [text.count(name) for name in _SSD_KERNELS] == [2, 1]
    assert text.count("tpu_custom_call") == 9
    for wrapper, sites in (("_in_fwd", 10), ("_in_bwd", 5),
                           ("_out_fwd", 10), ("_out_bwd", 5)):
        assert len(re.findall(rf"call @{wrapper}(_\d+)?\(", text)) \
            == sites, wrapper
    assert not re.search(r"tensor<1x8192x8x1024xf32>", text)


def _seam(norm, theta, d):
    """The seam at a cell's size, both directions: (yq, yk, yv and the
    two gains) -> the gradients of a sum of squares over q, k and v (a
    cotangent that needs the forward's values, or no forward runs)."""
    def fwd_bwd(yq, yk, yv, gq, gk):
        from horovod_tpu.ops.qk_prep import qk_prep

        B, T = yq.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))

        def loss(yq, yk, yv, gq, gk):
            gains = (gq, gk) if norm else (None, None)
            return sum((x.astype(F32) ** 2).sum() for x in qk_prep(
                yq, yk, yv, *gains, positions, theta, d, 1e-5))

        return jax.grad(loss, (0, 1, 2, 3, 4) if norm else (0, 1, 2))(
            yq, yk, yv, gq, gk)
    return fwd_bwd


@pytest.mark.parametrize("what, B, T, H, Hkv, d, norm, theta", [
    # Trinity-Mini's window layers; its full layer (no RoPE: the same
    # kernels on the table [1, 0]); Mistral's and OLMoE's; heads 256 wide
    ("norm a head and RoPE", 2, 8192, 32, 4, 128, True, 1e4),
    ("norm a head, no RoPE", 2, 8192, 32, 4, 128, True, None),
    ("RoPE alone, 8 KV heads", 2, 4096, 32, 8, 128, False, 1e6),
    ("heads of 256", 2, 8192, 16, 2, 256, True, 1e7),
])
def test_qk_prep_compiles_for_described_v5e(for_tpu, what, B, T, H, Hkv, d,
                                            norm, theta):
    """The seam's kernel pair at the chip cells' sizes as the chip's
    compiler takes it (a grid step of 256 tokens at full width, the VMEM
    it asks for itself), each kernel by the name a device trace shows."""
    text = for_tpu(_seam(norm, theta, d), ((B, T, H * d), BF16),
                   ((B, T, Hkv * d), BF16), ((B, T, Hkv * d), BF16),
                   ((d,), BF16), ((d,), BF16))
    for name in ("hvd_qk_prep_fwd", "hvd_qk_prep_bwd"):
        assert f'"kernel":"{name}"' in text, (what, name)


def _latent_seam(yq, ykv, k_r):
    """Latent attention's seam at Xing4.0's widths, both directions (as
    ``_seam``'s)."""
    from horovod_tpu.ops.mla_prep import mla_prep

    B, T = yq.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    freqs = 1e4 ** (-jnp.arange(0, 64, 2, dtype=F32) / 64)
    return jax.grad(lambda *a: sum(
        (x.astype(F32) ** 2).sum()
        for x in mla_prep(*a, positions, freqs, 1.2, 128)), (0, 1, 2))(
        yq, ykv, k_r)


def test_mla_prep_compiles_for_described_v5e(v5e_chip, for_tpu):
    """Latent attention's seam at the Xing4.0 cell's size (beside
    ``flash-fwd+bwd-xing4-b1s8192-192-128``: 8192 tokens, 32 heads 128 +
    64 beside 128 wide, so an odd head's slab starts in the middle of a
    lane tile) as the chip's compiler takes it, each kernel by the name a
    device trace shows and with the VMEM it asks for by itself: a step's
    blocks twice over (a 192-wide row of ``q`` and ``k`` lies 256 wide
    there) and 8 MiB for a group's values, under the chip's 128 MiB."""
    from horovod_tpu.ops import mla_prep

    lowered = jax.jit(_latent_seam).lower(*(
        jax.ShapeDtypeStruct((1, 8192, n), BF16, sharding=v5e_chip)
        for n in (32 * 192, 32 * 256, 64)))
    asked = [int(n) for n in re.findall(
        r'scoped_memory_configs[^}]*size\W+22\W+(\d+)', lowered.as_text())]
    blocks = mla_prep.TOKENS_A_STEP * (
        2 * (32 * (192 + 256 + 2 * 256 + 128) + 128) + 4 * 128)
    assert asked == [2 * blocks + (8 << 20)] * 2 and asked[0] < 100 << 20
    text = lowered.compile().as_text()
    for name in ("hvd_mla_prep_fwd", "hvd_mla_prep_bwd"):
        assert f'"kernel":"{name}"' in text, name


def test_latent_layers_lower_the_seam_once_a_direction(v5e_chip, monkeypatch):
    """Three latent-attention layers at 128 + 64 beside 128 under remat
    "attn", lowered for the described chip: the seam's forward kernel
    twice (the recomputation comes with a jaxpr of its own) and its
    backward once WHATEVER the layers, beside the flash pair; and no
    transpose makes a 192-wide ``[B, H, T, 192]`` (q, k) any more, none a
    second ``[B, H, T, 128]`` (v): what is left is ``do``'s way in."""
    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    cfg = LlamaConfig(vocab_size=512, d_model=256, n_layers=3, n_heads=2,
                      n_kv_heads=2, d_ff=512, q_lora_rank=64,
                      kv_lora_rank=64, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, dtype="bfloat16",
                      param_dtype="bfloat16", remat="attn")
    tokens = jax.ShapeDtypeStruct((2, 2048), I32, sharding=v5e_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_chip),
        jax.eval_shape(lambda k: llama_init(cfg, k), jax.random.PRNGKey(0)))
    text = jax.jit(jax.grad(lambda p, d: llama_loss(p, d, cfg))).lower(
        params, {"tokens": tokens, "targets": tokens}).as_text()
    assert text.count("hvd_mla_prep_fwd") == 2
    assert text.count("hvd_mla_prep_bwd") == 1
    assert "hvd_flash_fwd" in text and "hvd_flash_bwd_fused" in text
    made = set(re.findall(
        r"stablehlo\.transpose.*-> tensor<2x2x2048x(\d+)x", text))
    assert made == {"128"}, made


def test_layers_on_the_seam_lower_two_kernels_and_transpose_no_operand(
        v5e_chip, monkeypatch):
    """Three attention layers with a q/k norm a head, RoPE on two of
    them (Trinity-Mini's pattern, remat "attn"), lowered for the
    described chip: the seam's forward kernel twice (the recomputation
    comes with a jaxpr of its own) and its backward once WHATEVER the
    layers, the layer without RoPE among them; no transpose makes a
    ``[B, Hkv, T, d]`` (k, v) and one a layer makes a ``[B, H, T, d]``
    (``do``: ``o``'s way back is not the seam's; with the expressions
    the forward's q, the recomputed q and ``do``)."""
    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    S, Fl = "sliding_attention", "full_attention"
    cfg = LlamaConfig(vocab_size=512, d_model=512, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_head=128, d_ff=512, qk_norm="head",
                      layer_types=(S, S, Fl), sliding_window=1024,
                      dtype="bfloat16", param_dtype="bfloat16", remat="attn")
    tokens = jax.ShapeDtypeStruct((2, 2048), I32, sharding=v5e_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_chip),
        jax.eval_shape(lambda k: llama_init(cfg, k), jax.random.PRNGKey(0)))
    text = jax.jit(jax.grad(lambda p, d: llama_loss(p, d, cfg))).lower(
        params, {"tokens": tokens, "targets": tokens}).as_text()
    assert text.count("hvd_qk_prep_fwd") == 2
    assert text.count("hvd_qk_prep_bwd") == 1
    for wrapper, sites in (("_fwd", 6), ("_bwd", 3)):
        assert len(re.findall(rf"call @{wrapper}(_\d+)?\(", text)) \
            == sites, wrapper
    made = re.findall(r"stablehlo\.transpose.*-> tensor<2x(\d+)x2048x128x",
                      text)
    assert made == ["4"] * 3, made


def test_a_stack_under_remat_attn_holds_no_padded_statistics(for_tpu):
    """Three attention layers at Trinity-Mini's shape (B2 T8192, 32
    heads on 4 of 128), unrolled, each under
    ``save_only_these_names("flash_o", "flash_lse")`` as remat ``attn``
    saves them: the gradient program's temporaries. The parent's read
    2,220,851,200 B here, holding three ``f32[2,32,8192,1]`` residuals
    of 268,435,456 B each from forward to backward; the bound is that
    less the three (this tree reads 1,193,633,792: the transients went
    too)."""
    from horovod_tpu.ops import flash_attention

    B, T, H, HKV, D, DM, L = 2, 8192, 32, 4, 128, 2048, 3

    def layer(x, w_qkv, w_o):
        q, k, v = jnp.split(x @ w_qkv, [H * D, (H + HKV) * D], axis=-1)
        a = flash_attention(q.reshape(B, T, H, D), k.reshape(B, T, HKV, D),
                            v.reshape(B, T, HKV, D), causal=True)
        return x + a.reshape(B, T, H * D) @ w_o

    saved = jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(
            "flash_o", "flash_lse"))

    def loss(x, w_qkv, w_o):
        for i in range(L):
            x = saved(x, w_qkv[i], w_o[i])
        return x.astype(F32).sum()

    compiled = for_tpu.executable(
        jax.grad(loss, argnums=(0, 1, 2)), ((B, T, DM), BF16),
        ((L, DM, (H + 2 * HKV) * D), BF16), ((L, H * D, DM), BF16))
    assert not _LANE_PADDED.findall(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2_220_851_200 - 3 * 268_435_456


_LOCATION_FLAGS = ("jax_include_full_tracebacks_in_locations",
                   "jax_traceback_in_locations_limit",
                   "jax_compilation_cache_include_metadata_in_key")


@pytest.mark.parametrize("locations", ["whole", "cut"])
def test_flash_kernels_keep_their_names_in_the_compiled_program(
        for_tpu, locations, monkeypatch, tmp_path):
    """What a device trace shows of a kernel is its instruction in the
    compiled program, and the instruction's name follows the locations
    (``%tpu_custom_call.N`` until PR 36, the name stack's last piece
    since); the kernel metadata tells the kernels apart whether
    locations are whole (jax's default) or cut as
    ``enable_compile_cache()`` cuts them. Cut, the program names no
    caller: no file, no line, no frame, in the text or in the kernels
    serialized into it. The backward is ONE kernel (PR 28) whose name
    keeps the ``hvd_flash_bwd`` the benchmark's reader matches."""
    from horovod_tpu.utils import compile_cache

    was = {f: getattr(jax.config, f) for f in _LOCATION_FLAGS}
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        if locations == "cut":
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            compile_cache.enable_compile_cache()
        # (a fresh function: jax's lowering cache does not key on it)
        text = for_tpu(lambda *a: _flash_fwd_bwd(*a), _Q, _KV, _KV)
    finally:
        for f, v in was.items():
            jax.config.update(f, v)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    names = re.findall(
        r"custom-call\([^\n]*kernel_metadata=\{\s*\"kernel\":\"(\w+)\"\s*\}",
        text)
    assert sorted(names) == ["hvd_flash_bwd_fused", "hvd_flash_fwd"]
    names_a_caller = "test_chip_compile" in text or "stack_frame_id" in text
    assert names_a_caller == (locations == "whole")


def test_a_window_rides_in_the_kernel_metadata_and_nowhere_else(for_tpu):
    """A flash call with a window carries it beside the kernel's name
    (what ``flash_window_ms_per_step`` matches); one without compiles
    to the call it always was (name alone: the test above)."""
    text = for_tpu(lambda *a: (_flash_window_fwd_bwd(*a),
                               _flash_fwd_bwd(*a)), _TQ, _TKV, _TKV)
    found = re.findall(r"kernel_metadata=\{([^}]*)\}", text)
    found = sorted(set("".join(m.split()) for m in found))
    assert found == ['"kernel":"hvd_flash_bwd_fused"',
                     '"kernel":"hvd_flash_bwd_fused","window":"2048"',
                     '"kernel":"hvd_flash_fwd"',
                     '"kernel":"hvd_flash_fwd","window":"2048"']


def test_routed_rows_move_through_bare_gathers_on_the_v5e(for_tpu):
    """OLMoE's row movement (8192 tokens x 8 choices into 64 experts,
    ``ops/grouped_moe.py``), forward + backward, as the chip's compiler
    emits it: five gathers (dispatch and combine, their VJPs, the gate
    weights' gradient), two sorts, no scatter for the group count or in
    any VJP, and no select pass over a gathered ``[65536,2048]`` (what
    ``jnp.take``'s default mode costs there)."""
    from horovod_tpu.ops import grouped_moe

    S, K, D, E = 8192, 8, 2048, 64

    def rows(h, w, e_flat):
        def f(h, w):
            (order, inv), ws = grouped_moe._sort_slots(e_flat, w)
            tok, inv = order // K, inv.reshape(S, K)
            x = grouped_moe._dispatch(h, tok, inv) * ws[:, None]
            return (grouped_moe._combine(x, tok, inv).astype(F32).sum()
                    * grouped_moe._group_sizes(e_flat, E).sum())

        return jax.value_and_grad(f, (0, 1))(h, w)

    text = for_tpu(rows, ((S, D), BF16), ((S * K,), BF16), ((S * K,), I32))
    count = lambda op: len(re.findall(rf" {op}\(", text))   # noqa: E731
    assert (count("gather"), count("scatter"), count("sort")) == (5, 0, 2)
    assert not re.search(r"= bf16\[65536,2048\]\S* select\(", text)


@pytest.mark.parametrize("S, D, E, K, score, biased", [
    pytest.param(8192, 4096, 512, 22, "sigmoid", True, id="nemotron"),
    pytest.param(16384, 2048, 512, 10, "softmax", False, id="qwen3next"),
])
def test_the_router_picks_its_scores_with_no_gather_on_the_v5e(
        for_tpu, S, D, E, K, score, biased):
    """``moe_route``, forward + backward, at the two cells' shapes where
    the router weighs most, as the chip's compiler emits it (PR 54): the
    pick of the K chosen scores and its transpose are selects under a
    sum inside fusions (``models/llama.py:_pick``), so no gather, no
    scatter, and nothing ``[tokens, K, E]`` long between two fusions."""
    from horovod_tpu.models.llama import moe_route

    def route(h, w, bias, weights, tilt):
        def f(h, w):
            vals, idx, balance = moe_route(
                h, w, K, True, score, bias if biased else None, 5.0)
            return ((vals * weights).sum() + (balance * tilt).sum(),
                    idx)

        return jax.value_and_grad(f, (0, 1), has_aux=True)(h, w)

    text = for_tpu(route, ((1, S, D), BF16), ((D, E), F32), ((E,), F32),
                   ((1, S, K), F32), ((2, E), F32))
    count = lambda op: len(re.findall(rf" {op}\(", text))   # noqa: E731
    assert (count("gather"), count("scatter")) == (0, 0)
    fused = True                 # the computation a line stands in
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = "fused_computation" in line.split("(")[0]
        for dims in re.findall(r"= \(?\w+\[([\d,]+)\]", line):
            n = math.prod(map(int, dims.split(",")))
            assert fused or n < S * K * E, line


def test_a_shares_gather_buffer_is_born_in_a_branch_and_never_zero_filled(
        for_tpu):
    """The share's block-wise gather at the cells' sizes (a chunk of
    32,768 rows out of 16,384 tokens) as the chip's compiler emits it
    (PR 35): one ``while`` whose body gathers a block of
    ``bf16[2048,2048]``, inside a ``conditional``; the chunk's buffer is
    an ``AllocateBuffer`` in that branch's computation, not in the
    entry computation (allocated there, a step's 24 buffers all live
    from the program's start: 9.47 GB where 7.52 compile), and no
    zero-fill of a chunk stands in the branch that holds rows."""
    from horovod_tpu.ops import grouped_moe

    text = for_tpu(grouped_moe._gather_held, ((16384, 2048), BF16),
                   ((32768,), I32), ((), I32))
    entry = text[text.index("ENTRY "):]
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" conditional\(", entry)) == 1
    assert re.search(r"= bf16\[2048,2048\]\S* fusion\(.*kind=kCustom", text)
    assert not re.search(r"= bf16\[32768,2048\]\S* fusion\(.*kind=kCustom",
                         text)
    born = re.findall(r"= bf16\[32768,2048\]\S* custom-call\(\), "
                      r'custom_call_target="AllocateBuffer"', text)
    assert len(born) == 1 and "AllocateBuffer" not in entry
    assert len(re.findall(r"= bf16\[32768,2048\]\S* broadcast\(", text)) == 1


def test_a_shares_gradient_holds_nothing_stacked_by_chunk(for_tpu):
    """The gradient of a share's expert layer at the Qwen3-Next cell's
    shape (16,384 tokens, 32 of 512 experts held, 10 a token: 8 chunks
    of 20,480 slots) as the chip's compiler emits it (PR 41): the seven
    later chunks are one ``while`` forward and one backward (in the
    branch its accumulators are born in) whose trip count follows the
    rows held, with no tokens and no expert matrix
    stacked seven times as a residual (a differentiated ``lax.scan``
    over them carried 1.88 GB of those, 3.49 GB of temporaries in
    all)."""
    from horovod_tpu.models import LlamaConfig
    from horovod_tpu.ops import grouped_moe

    S, D, F, E, H, K = 16384, 2048, 512, 512, 32, 10
    cfg = LlamaConfig(vocab_size=512, d_model=D, n_layers=1, n_heads=16,
                      n_kv_heads=2, d_ff=F, n_experts=E,
                      n_experts_per_token=K, n_experts_held=H,
                      moe_impl="grouped", dtype="bfloat16",
                      param_dtype="bfloat16")

    def loss(hf, w, gate, up, down, idx):
        lp = {"moe_gate": gate, "moe_up": up, "moe_down": down}
        y = grouped_moe._held_experts_ffn(hf, lp, cfg, w, idx)
        return jnp.sum(jnp.square(y.astype(F32)))

    exe = for_tpu.executable(
        jax.grad(loss, (0, 1, 2, 3, 4)), ((S, D), BF16), ((S, K), BF16),
        ((H, D, F), BF16), ((H, D, F), BF16), ((H, F, D), BF16),
        ((S, K), I32))
    text = exe.as_text()
    loops = re.findall(r' while\(.*op_name="[^"]*?(transpose\()?jvp'
                       r'[^"]*hvd\.moe\.combine/(?:cond/branch_1_fun/)?while"',
                       text)
    assert sorted(loops) == ["", "transpose("], loops
    for stacked in ("[7,16384,2048]", "[7,32,2048,512]", "[7,32,512,2048]",
                    "[8,16384,2048]", "[8,32,2048,512]", "[8,32,512,2048]"):
        assert stacked not in text, stacked
    assert exe.memory_analysis().temp_size_in_bytes < 2.0e9


def test_grad_program_of_a_one_layer_llama_holds_one_flash_bwd_call(
        v5e_chip, monkeypatch):
    """``jit_hvd_grad`` as the split step lowers it for the described
    chip: one ``hvd_flash_bwd*`` call an attention layer (two before
    PR 28), one forward call (remat "attn+gate" saves its residuals)."""
    import optax

    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
    from horovod_tpu.parallel import make_split_train_step

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    cfg = LlamaConfig(vocab_size=512, d_model=256, n_layers=1, n_heads=2,
                      n_kv_heads=1, d_ff=512, dtype="bfloat16",
                      param_dtype="bfloat16", remat="attn+gate")
    ts = make_split_train_step(lambda p, d: llama_loss(p, d, cfg),
                               optax.adam(1e-3))
    tokens = jax.ShapeDtypeStruct((2, 4096), I32)
    carry = jax.eval_shape(
        lambda k: ts.init(llama_init(cfg, k)), jax.random.PRNGKey(0))
    carry, batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_chip),
        (carry, {"tokens": tokens, "targets": tokens}))
    text = jax.jit(ts.step).lower(carry, batch).as_text()
    assert "@hvd_grad" in text
    assert re.findall(r"hvd_flash_\w+", text).count("hvd_flash_fwd") == 1
    assert re.findall(r"hvd_flash_bwd\w*", text) == ["hvd_flash_bwd_fused"]


def test_no_copy_stands_between_the_stacked_experts_and_the_grouped_gemm(
        for_tpu, v5e_chip):
    """The gradient of a two-layer grouped expert model as the chip's
    compiler emits it (PR 33): the stacked expert matrices reach every
    ``gmm`` call through a ``bitcast`` of the parameter (a static slice
    in front of a Mosaic call compiled to a ``slice_bitcast_fusion``, a
    copy of every layer's matrices; a scan's ``dynamic-slice`` likewise),
    nothing but ``tgmm`` produces an array of one layer's expert shape,
    and each stacked gradient is written once (``pad_add_fusion``)."""
    from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

    # Stacks of 268 MB, as OLMoE's: smaller ones the compiler moves
    # through fast memory, with copies and custom calls of its own.
    L, E, D, F = 2, 64, 1024, 2048
    cfg = LlamaConfig(vocab_size=512, d_model=D, n_layers=L, n_heads=8,
                      n_kv_heads=8, d_ff=F, n_experts=E,
                      n_experts_per_token=2, moe_impl="grouped",
                      dtype="bfloat16", param_dtype="bfloat16", remat="moe")
    tokens = jax.ShapeDtypeStruct((2, 1024), I32)
    params = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    params, batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_chip),
        (params, {"tokens": tokens, "targets": tokens}))
    text = jax.jit(jax.grad(lambda p, b: llama_loss(p, b, cfg))).lower(
        params, batch).compile().as_text()
    entry = text[text.index("ENTRY "):]
    made = lambda shape: re.findall(                       # noqa: E731
        rf"^\s+(?:ROOT )?(\S+) = bf16\[{shape}\]\S* ([\w\-]+)\(", entry, re.M)
    for k, n in ((D, F), (F, D)):
        one_layer = made(f"{E},{k},{n}")
        assert one_layer and all(
            name.startswith("%tgmm") and op == "custom-call"
            for name, op in one_layer), one_layer
        assert {op for _, op in made(f"{L * E},{k},{n}")} == {"bitcast"}
        stacked = [(name, op) for name, op in made(f"{L},{E},{k},{n}")
                   if op != "parameter"]
        assert len(stacked) == (2 if (k, n) == (D, F) else 1), stacked
        assert all(op == "fusion" for _, op in stacked), stacked


def test_interpret_mode_on_tpu_operands_raises(monkeypatch):
    """A TPU run must never crawl through the pallas interpreter."""
    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode"):
        _platform.use_pallas("flash_attention", (), interpret=True)
    assert _platform.use_pallas("flash_attention", ()) is True


def test_kernel_choice_follows_the_operands_device():
    x = jnp.ones((2, 2))
    assert _platform.operand_platform(x) == "cpu"
    assert _platform.use_pallas("flash_attention", (x,)) is False
    assert _platform.use_pallas("flash_attention", (x,), interpret=True)
    assert jax.jit(lambda y: _platform.operand_platform(y) == "cpu")(x)


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_goes_where_the_environment_says(monkeypatch,
                                                       tmp_path):
    from horovod_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    flags = {f: getattr(jax.config, f) for f in _LOCATION_FLAGS}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # jax reads the variable itself; nothing is set in code
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert compile_cache.enable_compile_cache() == fixed  # never moves
        # No program keys on the caller's stack: a location is the
        # operation's name stack (which rides into the compiled text
        # and into the key) and no frame at all.
        text = jax.jit(jax.named_scope("a_scope")(lambda x: x * 2)).lower(
            jnp.ones(3)).as_text(debug_info=True)
        assert "a_scope/mul" in text and ".py" not in text
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        for f, v in flags.items():
            jax.config.update(f, v)


@pytest.mark.parametrize("table", ["bench-flops", "step-timer",
                                   "decode-bench-hbm"])
def test_peak_tables_know_this_chip_and_refuse_others(table, monkeypatch):
    from horovod_tpu.telemetry import step_timer
    from horovod_tpu.utils import devices

    class Device:
        def __init__(self, kind):
            self.device_kind = kind

    if table == "bench-flops":
        def lookup(kind):
            return devices.match_device_table(
                Device(kind), devices.PEAK_BF16_FLOPS)
        want = 197e12
    elif table == "step-timer":
        def lookup(kind):
            monkeypatch.setattr(jax, "devices", lambda: [Device(kind)])
            return step_timer._device_peak_flops()
        want = 197e12
    else:  # the table a decode step's bandwidth share is taken against
        def lookup(kind):
            return devices.match_device_table(
                Device(kind), devices.PEAK_HBM_BYTES_PER_S)
        want = 819e9
    assert lookup("TPU v5 lite") == want
    assert lookup("TPU v5e") == want
    for unknown in ("cpu", "TPU v9 ultra", ""):
        with pytest.raises(KeyError, match="not in the peak table"):
            lookup(unknown)
