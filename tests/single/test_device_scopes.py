"""The device scopes (docs/metrics.md "Device scopes"): the closed table
and its helper, that the scopes survive ``jax.checkpoint`` into the
compiled program under the locations ``enable_compile_cache()`` sets up
(and do not under the flag it set before PR 36), that they change no
program and name no caller, and ``scope_table`` on hand-written text.
CPU; tiny configurations of the shapes the benchmark's cells have."""

import hashlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.utils import spans

pytestmark = pytest.mark.quick

_FLAGS = ("jax_include_full_tracebacks_in_locations",
          "jax_traceback_in_locations_limit",
          "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def cache_locations(monkeypatch, tmp_path):
    """The locations ``enable_compile_cache()`` sets up, for one test."""
    from horovod_tpu.utils import compile_cache

    was = {f: getattr(jax.config, f) for f in _FLAGS}
    cache_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable_compile_cache()
    yield
    for f, v in was.items():
        jax.config.update(f, v)
    jax.config.update("jax_compilation_cache_dir", cache_dir)


S, F, C = "sliding_attention", "full_attention", "conv"
_SHARE = dict(vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2,
              d_ff=96, moe_d_ff=32, n_experts_per_token=4,
              n_dense_layers=1, score_func="sigmoid", qk_norm="head",
              moe_impl="grouped", moe_aux_weight=0.0, dtype="float32",
              param_dtype="float32")
CONFIGS = {
    # a dense scanned stack under the two remat modes dense cells use
    "dense-scan-attn+gate": (dict(remat="attn+gate"),
                             ["hvd.ffn", "hvd.attn.proj", "hvd.norm"]),
    "dense-scan-attn": (dict(remat="attn"),
                        ["hvd.ffn", "hvd.attn.proj", "hvd.attn.rope"]),
    # a grouped expert stack, unrolled, no grouped matmul recomputed
    "grouped-moe": (dict(n_experts=4, moe_impl="grouped", remat="moe"),
                    ["hvd.attn.proj", "hvd.moe.route"]),
    # a share of the experts, window layers, a shared expert (afmoe)
    "share-window-attn": (dict(
        _SHARE, n_layers=3, d_head=32, n_experts=16, first_expert=4,
        n_experts_held=4, layer_types=(S, S, F), sliding_window=6,
        n_shared_experts=1, attn_gate=True, post_norm=True,
        remat="attn"), ["hvd.ffn", "hvd.attn.proj", "hvd.moe.experts"]),
    # a share beside conv layers, the head tied (lfm2)
    "share-conv-attn": (dict(
        _SHARE, n_layers=3, n_experts=8, first_expert=2, n_experts_held=2,
        layer_types=(C, F, C), conv_taps=3, rope_full_attention=True,
        tie_embeddings=True, remat="attn"),
        ["hvd.ffn", "hvd.attn.proj", "hvd.conv.proj", "hvd.conv.chain"]),
}


def _grad_text(cfg):
    c = LlamaConfig.tiny(**cfg)
    params = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def hvd_grad(p, d):
        return jax.value_and_grad(lambda p, d: llama_loss(p, d, c))(p, d)

    return jax.jit(hvd_grad).lower(
        params, {"tokens": tok, "targets": tok}).compile().as_text()


def _matmuls(text):
    """Names of every ``dot`` outside a fusion and of every fusion that
    holds one."""
    holders, current, names = {}, None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            current = head.group(1)
        m = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? dot\(", line)
        if m:
            holders.setdefault(current, []).append(m.group(1))
    fused = {}
    for m in re.finditer(r"%?([\w.\-]+) = [^\n]*? fusion\([^\n]*calls=%?"
                         r"([\w.\-]+)", text):
        fused[m.group(2)] = m.group(1)
    for computation, dots in holders.items():
        names += [fused[computation]] if computation in fused else dots
    return names


def test_the_table_is_closed_and_the_module_a_leaf():
    with pytest.raises(ValueError, match="not a device scope"):
        spans.scope("hvd.anything")
    with pytest.raises(ValueError, match="not a device scope"):
        spans.scope("hvd.wait")            # a host span is no scope
    assert not spans.SCOPES & spans.SPANS
    code = ("import sys; from horovod_tpu.utils.spans import scope; "
            "scope('hvd.ffn'); assert not [m for m in sys.modules if "
            "m.startswith('horovod_tpu.telemetry')]")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_only_the_helper_opens_a_named_scope():
    import os

    import horovod_tpu

    root = os.path.dirname(horovod_tpu.__file__)
    found = []
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if "named_scope(" in fh.read():
                        found.append(os.path.relpath(os.path.join(d, f),
                                                     root))
    assert found == ["utils/spans.py"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_matmul_has_a_scope_and_remat_keeps_the_phases(
        cache_locations, name):
    cfg, all_three = CONFIGS[name]
    text = _grad_text(cfg)
    table = spans.scope_table(text)
    matmuls = _matmuls(text)
    assert len(matmuls) >= 12
    assert [n for n in matmuls if table[n].scope is None] == []
    phases = {}
    for scoped in table.values():
        phases.setdefault(scoped.scope, set()).add(scoped.phase)
    for scope in all_three:
        assert phases.get(scope) == set(spans.PHASES), (scope, phases)
    assert "stack_frame_id" not in text     # no frame behind a name


def test_the_flag_before_pr_36_lost_the_scopes():
    """Motivation's finding, pinned: with locations cut the old way
    ``op_name`` is the bare primitive, the name stack sits behind a
    ``stack_frame_id`` (and not at all for what ``jax.checkpoint``
    re-emits): no matmul resolves to a scope and nothing under one
    reads recomputed."""
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        text = _grad_text(dict(CONFIGS["dense-scan-attn"][0], n_layers=3))
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    assert "stack_frame_id" in text and "StackFrames" in text
    table = spans.scope_table(text)
    matmuls = _matmuls(text)
    assert len(matmuls) >= 12
    assert {table[n].scope for n in matmuls} == {None}
    assert "recomputed" not in {s.phase for s in table.values() if s.scope}


def test_one_step_from_two_call_stacks_is_one_text(cache_locations):
    c = LlamaConfig.tiny(remat="attn+gate")
    params = jax.eval_shape(lambda k: llama_init(c, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def lower():
        return jax.jit(jax.grad(lambda p, d: llama_loss(p, d, c))).lower(
            params, {"tokens": tok, "targets": tok})

    def deeper(n):
        return deeper(n - 1) if n else lower()

    a = lower().as_text(debug_info=True)
    b = deeper(3).as_text(debug_info=True)
    assert a == b
    assert ".py" not in a and "hvd.ffn" in a


_STRIPPED = r"""
import contextlib, hashlib, re, sys
import jax, jax.numpy as jnp
from horovod_tpu.utils import spans
if sys.argv[1] == "without":
    class _Null(contextlib.ContextDecorator):
        def __enter__(self):
            return self
        def __exit__(self, *exc):
            return False
    spans.scope = lambda name: _Null()
from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.parallel import make_split_train_step
S, F, C = "sliding_attention", "full_attention", "conv"
c = LlamaConfig.tiny(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
    moe_d_ff=32, n_experts_per_token=4, n_dense_layers=1,
    score_func="sigmoid", qk_norm="head", moe_impl="grouped",
    moe_aux_weight=0.0, dtype="float32", param_dtype="float32",
    n_layers=4, n_experts=8, first_expert=2, n_experts_held=2,
    layer_types=(C, F, C, S), sliding_window=6, conv_taps=3,
    n_shared_experts=1, remat="attn")
params = jax.eval_shape(lambda k: llama_init(c, k),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
text = jax.jit(jax.value_and_grad(lambda p, d: llama_loss(p, d, c))).lower(
    params, {"tokens": tok, "targets": tok}).compile().as_text()
scoped = len(re.findall(r'op_name="[^"]*hvd\.', text))
text = re.sub(r", metadata=\{[^}]*\}", "", text)
print(scoped, hashlib.sha256(text.encode()).hexdigest())
"""


def test_the_scopes_change_no_program():
    """The grad program with its metadata stripped is the same text,
    instruction names included, with ``spans.scope`` a no-op."""
    got = {}
    for mode in ("with", "without"):
        out = subprocess.run([sys.executable, "-c", _STRIPPED, mode],
                             check=True, timeout=300, capture_output=True,
                             text=True).stdout.split()
        got[mode] = (int(out[0]), out[1])
    assert got["with"][0] > 100 and got["without"][0] == 0
    assert got["with"][1] == got["without"][1]


# ``scope_table`` on hand-written text.
HLO = r"""HloModule jit_hvd_grad, is_scheduled=true

%fused_computation.1 (p.1: f32[8,8], p.2: f32[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  %p.2 = f32[8,8]{1,0} parameter(1)
  %mul.1 = f32[8,8]{1,0} multiply(%p.1, %p.1), metadata={op_name="jit(hvd_grad)/jvp(hvd.norm)/mul"}
  ROOT %dot.1 = f32[8,8]{1,0} dot(%mul.1, %p.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(hvd_grad)/jvp(hvd.attn.proj)/dot_general"}
}

%fused_computation.2 (p.3: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p.3 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p.3), metadata={op_name="jit(hvd_grad)/transpose(jvp(hvd.ffn))/checkpoint/rematted_computation/hvd.ffn/exp"}
  %neg.1 = f32[8,8]{1,0} negate(%exp.1), metadata={op_name="jit(hvd_grad)/transpose(jvp(hvd.ffn))/checkpoint/rematted_computation/hvd.ffn/neg"}
  ROOT %tuple.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%neg.1, %exp.1)
}

%fused_computation.3 (p.4: f32[8,8]) -> f32[8,8] {
  %p.4 = f32[8,8]{1,0} parameter(0)
  ROOT %add.9 = f32[8,8]{1,0} add(%p.4, %p.4), metadata={op_name="jit(hvd_grad)/transpose(jvp())/add_any"}
}

%fused_computation.4 (p.5: f32[8,8], p.6: f32[8,8]) -> f32[8,8] {
  %p.5 = f32[8,8]{1,0} parameter(0)
  %p.6 = f32[8,8]{1,0} parameter(1)
  %convolution.1 = f32[8,8]{1,0} convolution(%p.5, %p.6), dim_labels=bf_io->bf, metadata={op_name="jit(hvd_grad)/transpose(jvp(jvp()))/checkpoint/hvd.ffn/dot_general"}
  ROOT %mul.9 = f32[8,8]{1,0} multiply(%convolution.1, %p.5), metadata={op_name="jit(hvd_grad)/transpose(jvp(jvp()))/checkpoint/hvd.norm/mul"}
}

%fused_computation.5 (p.7: f32[8,8]) -> f32[8,8] {
  %p.7 = f32[8,8]{1,0} parameter(0)
  ROOT %fusion.6 = f32[8,8]{1,0} fusion(%p.7), kind=kCustom, calls=%fused_computation.6
}

%fused_computation.6 (p.8: f32[8,8]) -> f32[8,8] {
  %p.8 = f32[8,8]{1,0} parameter(0)
  ROOT %scatter.1 = f32[8,8]{1,0} scatter(%p.8, %p.8, %p.8), to_apply=%cond.1, metadata={op_name="jit(hvd_grad)/transpose(jvp(hvd.moe.route))/scatter-add"}
}

%body.1 (arg.1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%arg.1), index=1
  %gather.1 = f32[8,8]{1,0} gather(%gte.1, %gte.1), offset_dims={1}, metadata={op_name="jit(hvd_grad)/jvp(hvd.moe.dispatch)/while/body/gather"}
  ROOT %tuple.2 = (s32[], f32[8,8]{1,0}) tuple(%gte.1, %gather.1)
}

%cond.1 (arg.2: (s32[], f32[8,8])) -> pred[] {
  %arg.2 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%arg.2, %arg.2), direction=LT
}

%branch.1 (arg.3: f32[8,8]) -> f32[8,8] {
  %arg.3 = f32[8,8]{1,0} parameter(0)
  ROOT %zeros.1 = f32[8,8]{1,0} broadcast(%arg.3), dimensions={}, metadata={op_name="jit(hvd_grad)/transpose(jvp(hvd.moe.dispatch/hvd.moe.combine))/cond/branch_1_fun/broadcast_in_dim"}
}

ENTRY %main.1 (a.1: f32[8,8]) -> f32[8,8] {
  %a.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a.1, %a.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(hvd_grad)/jvp(hvd.attn.proj)/dot_general"}
  %fusion.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3
  %copy.1 = f32[8,8]{1,0} copy(%fusion.3)
  %fusion.4 = f32[8,8]{1,0} fusion(%copy.1, %copy.1), kind=kOutput, calls=%fused_computation.4
  %fusion.5 = f32[8,8]{1,0} fusion(%fusion.4), kind=kCustom, calls=%fused_computation.5
  %call.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) custom-call(%fusion.5), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"kernel":"hvd_flash_fwd"
}}, metadata={op_name="jit(hvd_grad)/jvp(hvd.attn.core)/pallas_call"}, backend_config={"x":"y"}
  %while.1 = (s32[], f32[8,8]{1,0}) while(%copy.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(hvd_grad)/jvp(hvd.moe.dispatch)/while"}
  ROOT %conditional.1 = f32[8,8]{1,0} conditional(%copy.1, %copy.1, %copy.1), branch_computations={%branch.1, %branch.1}, metadata={op_name="jit(hvd_grad)/transpose(jvp(hvd.moe.dispatch/hvd.moe.combine))/cond"}
}
"""

@pytest.mark.parametrize("name,want", [
    # a fusion is its ROOT; the norm folded into it makes it mixed
    ("fusion.1", ("hvd.attn.proj", "forward", True)),
    # a root without a scope of its own (a tuple): what it is made from
    ("fusion.2", ("hvd.ffn", "recomputed", False)),
    # no scope anywhere: None, and the phase still read
    ("fusion.3", (None, "backward", False)),
    # no metadata at all
    ("copy.1", (None, "forward", False)),
    ("while.1", ("hvd.moe.dispatch", "forward", False)),
    ("gather.1", ("hvd.moe.dispatch", "forward", False)),   # a body's
    # innermost of two nested scopes, in a conditional's branch
    ("zeros.1", ("hvd.moe.combine", "backward", False)),
    ("conditional.1", ("hvd.moe.combine", "backward", False)),
    ("mul.1", ("hvd.norm", "forward", False)),   # inside a fusion: own
    # built round a matmul: the matmul's scope, whatever the root is
    ("fusion.4", ("hvd.ffn", "backward", True)),
    # a fusion whose root is a fusion: resolved through both
    ("fusion.5", ("hvd.moe.route", "backward", False)),
    # an instruction printed over three lines (a Mosaic call)
    ("call.1", ("hvd.attn.core", "forward", False)),
])
def test_scope_table_on_hand_written_text(name, want):
    assert tuple(spans.scope_table(HLO)[name]) == want


def test_scope_table_knows_no_stranger():
    assert spans.scope_table(HLO).get("fusion.77") is None
    assert spans.read_name_stack("jit(f)/jvp(hvd.nothing)/mul") \
        == (None, "forward")
    assert spans.read_name_stack(
        "jit(f)/transpose(jvp(hvd.nothing))/mul") == (None, "backward")


def test_programs_file_themselves_once_and_the_cache_answers():
    """``scope_tables()`` after two steps: both programs under their
    module names, nothing compiled a second time, and from the second
    call on the step reaches the jitted functions bare."""
    import optax

    from horovod_tpu.parallel import make_split_train_step

    spans._PROGRAMS.clear()
    c = LlamaConfig.tiny(remat="attn", n_layers=2)
    ts = make_split_train_step(lambda p, b: llama_loss(p, b, c),
                               optax.sgd(0.1))
    tok = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": tok, "targets": tok}
    carry = ts.init(llama_init(c, jax.random.PRNGKey(0)))
    assert spans._PROGRAMS == {}        # nothing before the first call
    for _ in range(2):
        _, carry = ts.step(carry, batch)
    assert sorted(n for n, _ in spans._PROGRAMS) == ["hvd_apply",
                                                     "hvd_grad"]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    tables = spans.scope_tables()
    assert compiles == []
    assert sorted(tables) == ["jit_hvd_apply", "jit_hvd_grad"]
    applied = {s.scope for s in tables["jit_hvd_apply"].values()}
    assert "hvd.apply" in applied
    recomputed = {s.scope for s in tables["jit_hvd_grad"].values()
                  if s.phase == "recomputed"}
    assert {"hvd.ffn", "hvd.attn.proj", "hvd.norm"} <= recomputed
    spans._PROGRAMS.clear()


def test_same_name_programs_merge_and_disagreement_reads_none():
    a = {"x": spans.Scoped("hvd.allreduce", "forward", False),
         "y": spans.Scoped("hvd.allreduce", "forward", False)}
    b = {"x": spans.Scoped("hvd.allreduce", "forward", False),
         "y": spans.Scoped("hvd.apply", "forward", False),
         "z": spans.Scoped(None, "forward", False)}
    merged = spans.merge_tables([a, b])
    assert merged["x"].scope == "hvd.allreduce" and merged["y"].scope is None
    assert set(merged) == {"x", "y", "z"}
