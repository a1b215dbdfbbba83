"""A uniform stack of grouped expert layers runs unrolled, on static
indices of the stacked parameters; a dense stack and a pipeline stage
keep their ``lax.scan`` (``models/llama.py:_run_layers``; PERF.md
section 6, PR 33). The values are the scan's; only the indexing, and
with it the copies in front of and behind the grouped GEMMs, differ."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import llama
from horovod_tpu.models.llama import (LlamaConfig, llama_forward,
                                      llama_init, llama_loss)

pytestmark = pytest.mark.quick

E, D, F = 8, 64, 128


def _moe(n_layers=2, remat="moe", moe_impl="grouped"):
    return LlamaConfig.tiny(n_experts=E, n_experts_per_token=3,
                            moe_impl=moe_impl, dtype="float32",
                            n_layers=n_layers, remat=remat)


def _batch(cfg, shape=(2, 16)):
    tokens = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _scan_layers(params, x, c, mesh, seq_axis):
    """What ``_run_layers`` was for every uniform stack: the reference."""
    return lax.scan(llama._build_layer_body(c, mesh, seq_axis), x,
                    params["layers"])


def _loss_logits_grads(cfg, params, batch):
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg)))(params)
    logits = jax.jit(lambda p: llama_forward(p, batch["tokens"], cfg))(
        params)
    return loss, logits, grads


@pytest.mark.parametrize("remat", ["moe", "none"])
@pytest.mark.parametrize("n_layers", [2, 3])
def test_unrolled_expert_stack_equals_the_scan(n_layers, remat, monkeypatch):
    cfg = _moe(n_layers, remat)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, logits, grads = _loss_logits_grads(cfg, params, batch)
    monkeypatch.setattr(llama, "_run_layers", _scan_layers)
    ref_loss, ref_logits, ref_grads = _loss_logits_grads(cfg, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-6)
    jax.tree_util.tree_map_with_path(
        lambda path, g, ref: np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref), rtol=1e-5, atol=1e-6,
            err_msg=jax.tree_util.keystr(path)),
        grads, ref_grads)


def _scans(fn, *args):
    return len(re.findall(r"= scan\[", str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.parametrize("which, forward, grad", [
    ("grouped", 0, 0),          # unrolled: no scan anywhere
    ("auto-no-mesh", 0, 0),     # "auto" with no mesh IS the grouped path
    ("gshard", 1, 2),           # einsums: XLA fuses the indexing
    ("dense", 1, 2),            # forward scan + its transpose
])
def test_which_stacks_scan(which, forward, grad):
    cfg = {"grouped": _moe(),
           "auto-no-mesh": _moe(moe_impl="auto"),
           "gshard": _moe(moe_impl="gshard", remat="attn"),
           "dense": LlamaConfig.tiny(dtype="float32")}[which]
    params = llama_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    assert _scans(lambda p: llama_forward(p, batch["tokens"], cfg),
                  params) == forward
    assert _scans(jax.grad(lambda p: llama_loss(p, batch, cfg)),
                  params) == grad


def test_a_pipeline_stage_of_expert_layers_still_scans():
    """A stage is one layer program by contract, whatever the layer."""
    cfg = _moe()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((2, 16, cfg.d_model), jnp.float32)
    stage = llama._stage_scan(llama._build_layer_body(cfg, None, None))
    assert _scans(stage, params["layers"], x) == 1
    assert _scans(jax.grad(lambda lp: stage(lp, x)[0].sum()),
                  params["layers"]) == 2


def _grad_hlo(cfg):
    params = llama_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    return jax.jit(jax.grad(lambda p: llama_loss(p, batch, cfg))).lower(
        params).compile().as_text()


@pytest.mark.parametrize("n_layers", [2, 3])
def test_no_dynamic_indexing_of_the_stacked_experts_in_the_cpu_hlo(n_layers):
    """In the compiled gradient no ``dynamic-slice`` /
    ``dynamic-update-slice`` reads or writes an array of the stacked
    expert shape, and each stacked expert gradient is written by ONE
    instruction (the layers' gradients padded and added in one fusion),
    not layer by layer into a zero-filled stack."""
    text = _grad_hlo(_moe(n_layers))
    stacked = {f"f32[{n_layers},{E},{D},{F}]": 2,    # moe_gate, moe_up
               f"f32[{n_layers},{E},{F},{D}]": 1}    # moe_down
    for line in text.splitlines():
        if re.search(r" dynamic-(update-)?slice\(", line):
            assert not any(s in line for s in stacked), line
    # The entry computation's instructions (fusion bodies apart): who
    # produces an array of a stacked expert shape.
    entry = text[text.index("ENTRY "):]
    for shape, leaves in stacked.items():
        writers = re.findall(
            rf"^\s+(?:ROOT )?\S+ = {re.escape(shape)}\S* (\w[\w\-]*)\(",
            entry, re.M)
        writers = [w for w in writers if w != "parameter"]
        assert len(writers) == leaves, (shape, writers)


def test_the_scan_it_replaced_did_index_dynamically(monkeypatch):
    """The control of the test above: under ``lax.scan`` the same
    gradient reads the stacked experts through ``dynamic-slice`` and
    writes their gradient through ``dynamic-update-slice``."""
    monkeypatch.setattr(llama, "_run_layers", _scan_layers)
    text = _grad_hlo(_moe(3))
    shapes = (f"f32[3,{E},{D},{F}]", f"f32[3,{E},{F},{D}]")
    hits = [line for line in text.splitlines()
            if re.search(r" dynamic-(update-)?slice\(", line)
            and any(s in line for s in shapes)]
    assert hits


# --- the whole stack handed to the grouped GEMM (ops/grouped_moe.py) ---

_SIZES = [200, 0, 57, 255]     # uneven, one empty group; M = 512 rows


def _mm_operands(L=3, K=128, N=256):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    M, G = sum(_SIZES), len(_SIZES)
    return (jax.random.normal(k1, (M, K), jnp.float32),
            jax.random.normal(k2, (L, G, K, N), jnp.float32),
            jax.random.normal(k3, (M, N), jnp.float32),
            jnp.asarray(_SIZES, jnp.int32))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_grouped_mm_on_a_layer_of_a_stack_equals_it_on_the_slice(layer):
    """Forward, ``dlhs`` and ``drhs`` (the stack's gradient: the slice's
    at ``layer``, zero elsewhere), on the one-hot reference path."""
    from horovod_tpu.ops.grouped_moe import LayerOfStack, _grouped_mm

    lhs, stack, cot, sizes = _mm_operands()
    out, vjp = jax.vjp(
        lambda a, w: _grouped_mm(a, LayerOfStack(w, layer), sizes),
        lhs, stack)
    ref, ref_vjp = jax.vjp(lambda a, w: _grouped_mm(a, w, sizes),
                           lhs, stack[layer])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    (dlhs, dstack), (ref_dlhs, ref_drhs) = vjp(cot), ref_vjp(cot)
    np.testing.assert_array_equal(np.asarray(dlhs), np.asarray(ref_dlhs))
    np.testing.assert_array_equal(np.asarray(dstack[layer]),
                                  np.asarray(ref_drhs))
    others = np.delete(np.asarray(dstack), layer, axis=0)
    assert not others.any()


def test_a_layer_of_a_stack_converts_the_slice_not_the_stack():
    from horovod_tpu.ops.grouped_moe import LayerOfStack

    _, stack, _, _ = _mm_operands()
    of = LayerOfStack(stack, 1)
    assert of.astype(jnp.float32) is of
    half = of.astype(jnp.bfloat16)
    assert half.shape == stack.shape[1:] and half.dtype == jnp.bfloat16


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["forward", "dlhs"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_megablox_reads_one_layers_groups_out_of_the_stack(layer,
                                                           transpose_rhs):
    """The kernel itself (pallas interpret mode; the chip's run is the
    benchmark's ``check_grouped_mm`` and PERF.md section 6, PR 33):
    ``gmm`` over the stack's L*E groups, all but ``layer``'s empty,
    equals ``gmm`` over the slice bit for bit."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from horovod_tpu.ops.grouped_moe import _one_layers_groups

    lhs, stack, cot, sizes = _mm_operands()
    flat, padded = _one_layers_groups(stack, sizes, layer)
    assert flat.shape == (12, 128, 256)
    assert padded.tolist() == [0] * (4 * layer) + _SIZES \
        + [0] * (4 * (2 - layer))
    rows = cot if transpose_rhs else lhs
    kw = dict(preferred_element_type=jnp.float32, tiling=(128, 128, 128),
              transpose_rhs=transpose_rhs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(gmm(rows, flat, padded, **kw)),
        np.asarray(gmm(rows, stack[layer], sizes, **kw)))


@pytest.mark.parametrize("remat, saved", [("moe", True), ("attn+moe", True),
                                          ("attn", False)])
def test_the_routing_choice_is_saved_with_the_order_it_gave(
        remat, saved, monkeypatch, capsys):
    """A remat mode that saves the sorted order (``moe_perm``) saves the
    top-k choice it was sorted from: a backward that chose again would
    count other group sizes, cut the saved order at other rows and hand
    a slot's gate gradient to another expert wherever the recomputation
    rounds a near-tie the other way (on the chip the unrolled program
    did: PERF.md section 6, PR 33). Read on the kernels' branch, traced
    only."""
    from jax.ad_checkpoint import print_saved_residuals

    from horovod_tpu.ops import _platform

    monkeypatch.setattr(_platform, "operand_platform", lambda *a: "tpu")
    cfg = LlamaConfig.tiny(n_experts=E, n_experts_per_token=2,
                           moe_impl="grouped", dtype="float32", n_layers=1,
                           remat=remat, n_heads=1, n_kv_heads=1,
                           d_model=128, d_ff=128)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, (2, 128))
    print_saved_residuals(lambda p: llama_loss(p, batch, cfg), params)
    out = capsys.readouterr().out
    assert ("named 'moe_perm'" in out) == saved
    assert ("i32[256,2] named 'moe_gate_idx'" in out) == saved
