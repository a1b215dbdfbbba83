"""MiniCPM-SALA's layers through the program's normal path
(``LlamaConfig`` -> ``llama_init`` -> ``llama_loss``) against the plain
float32 reference (``horovod_tpu/models/reference.py: sala_*``) on seeded
weights, at a small size on the CPU: one published period (a
``sparse_attention`` layer, three ``lightning_attention`` layers) under
the three muP scalings, the selection's sizes scaled down together so
that blocks ARE left out (16 blocks of 16 keys, 4 chosen).

Float32 compute: program and reference then differ in the order of
float32 additions (the recurrence's chunks against tokens, the FFN's and
the head's blocks, the masked rows): 2e-5 of a logit or a loss, 1e-4
(l2) of a gradient leaf through four layers. The same reference on
weights rounded to bf16 stands a hundred tolerances off
(``test_bf16_in_the_references_place_is_refused``)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_forward, llama_init, \
    llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models import reference as ref
from horovod_tpu.models.llama import llama_partition_rules
from horovod_tpu.ops import sparse_attention as sa
from horovod_tpu.ops.ssd import ssd

pytestmark = pytest.mark.quick
F32 = jnp.float32
S, L = "sparse_attention", "lightning_attention"
TOL, GRAD_TOL = 2e-5, 1e-4
T = 256
SELECTION = dict(sparse_block=16, sparse_topk=4, sparse_kernel=8,
                 sparse_stride=4, sparse_init_blocks=1,
                 sparse_window_blocks=2)
LIGHTNING = dict(lightning_heads=4, lightning_head_dim=16,
                 lightning_chunk=64, lightning_depth=32)


def _cfg(layer_types=(S, L, L, L), **kw):
    base = dict(vocab_size=256, d_model=64, n_layers=len(layer_types),
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                layer_types=layer_types, qk_norm="head", attn_gate=True,
                rope_theta=10000.0, norm_eps=1e-6, dtype="float32",
                remat="attn/ffn", embed_mult=12.0,
                residual_mult=1.4 / 32 ** 0.5, logit_div=16.0,
                ffn_chunk=128, loss_chunk=128,
                **(SELECTION if S in layer_types else {}),
                **(LIGHTNING if L in layer_types else {}))
    base.update(kw)
    return LlamaConfig.tiny(**base)


def _params(cfg, seed=0):
    """Seeded weights with every gain moved off its start, so that a
    dropped or misplaced one shows."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))
    return jax.tree.map(
        lambda w: w + 0.05 * jax.random.normal(next(keys), w.shape, w.dtype)
        if w.ndim <= 2 else w, params)


def _batch(cfg, shape=(1, T), seed=2):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _loss_and_grads(loss, params):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


def _worst_leaf(got, want):
    errs = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w)
                           / (jnp.linalg.norm(w) + 1e-30)), got, want)
    path, err = max(jax.tree_util.tree_leaves_with_path(errs),
                    key=lambda kv: kv[1])
    return err, jax.tree_util.keystr(path)


@functools.lru_cache(maxsize=None)
def _period():
    """-> (cfg, params, batch, the program's loss and gradients, the
    reference's): one period, computed once for the cases below."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    return (cfg, params, batch,
            _loss_and_grads(lambda p: llama_loss(p, batch, cfg), params),
            _loss_and_grads(lambda p: ref.sala_loss(p, batch, cfg), params))


def test_loss_and_every_gradient_leaf_against_the_reference():
    cfg, params, batch, (loss, grads), (want, want_grads) = _period()
    assert abs(float(loss) - float(want)) < TOL * float(want)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    err, where = _worst_leaf(grads, want_grads)
    assert err < GRAD_TOL, (where, err)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.linalg.norm(g)) > 0, path


def test_logits_against_the_reference_and_blocks_are_left_out():
    cfg, params, batch, _, _ = _period()
    def reference(p):
        chosen = []     # the reference appends each sparse layer's sets
        return ref.sala_forward(p, batch["tokens"], cfg, chosen), chosen

    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: llama_forward(p, batch["tokens"], cfg))(
            params)
        want, chosen = jax.jit(reference)(params)
    assert float(jnp.max(jnp.abs(logits - want))
                 / jnp.max(jnp.abs(want))) < TOL
    per_token = np.asarray(chosen[0]).sum(-1)
    begun = np.arange(T) // 16 + 1
    assert (per_token == np.minimum(begun, 4)[None, :, None]).all()
    assert per_token.max() == 4 < T // 16


def test_bf16_in_the_references_place_is_refused():
    """The reference on weights rounded to bfloat16 (the nearest
    precision below) no longer agrees: a hundred tolerances off."""
    cfg, params, batch, (loss, grads), _ = _period()
    rounded = jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(F32),
                           params)
    want, want_grads = _loss_and_grads(
        lambda p: ref.sala_loss(p, batch, cfg), rounded)
    off = max(abs(float(loss) - float(want)) / float(want) / TOL,
              _worst_leaf(grads, want_grads)[0] / GRAD_TOL)
    assert off > 1e2, off


def _qk(cfg, params, batch):
    """The sparse layer's q and k as the reference forms them."""
    lp = jax.tree.map(lambda w: w[0].astype(F32), params["sparse_layers"])
    x = cfg.embed_mult * params["embed"][batch["tokens"]]
    h = ref._rms(x, lp["attn_norm"], cfg.norm_eps)
    b, t, _ = h.shape
    q = ref._rms((h @ lp["wq"]).reshape(b, t, cfg.n_heads, -1),
                 lp["q_norm"], cfg.norm_eps)
    k = ref._rms((h @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, -1),
                 lp["k_norm"], cfg.norm_eps)
    v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, -1)
    return q, k, v


def test_the_selection_as_sets_is_the_references():
    """Equal as SETS, every token and group, at float32 on the CPU."""
    cfg, params, batch, _, _ = _period()
    with jax.default_matmul_precision("highest"):
        q, k, _ = _qk(cfg, params, batch)
        table = jax.jit(lambda q, k: sa.select_blocks(
            q, k, block=16, topk=4, kernel=8, stride=4, init_blocks=1,
            window_blocks=2))(q, k)
        want = ref.sala_selection(q, k, cfg)
    got = np.asarray(sa.chosen(table))
    assert got.shape == want.shape == (1, T, 2, T // 16)
    assert (got == np.asarray(want)).all()
    # block 0 and the last two begun blocks are in every set
    t = np.arange(T)
    assert got[0, :, :, 0].all() and got[0, t, :, t // 16].all()
    assert got[0, t[16:], :, t[16:] // 16 - 1].all()


def test_a_full_topk_is_the_dense_gated_layer():
    """With ``topk`` at least the number of blocks every begun block is
    chosen: the sparse layer equals the dense ``attn_gate`` layer, which
    a sequence of up to ``sparse_dense_len`` runs, to rounding."""
    cfg = _cfg((S, L), sparse_topk=T // 16)
    dense = dataclasses.replace(cfg, sparse_dense_len=T)
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = _loss_and_grads(lambda p: llama_loss(p, batch, cfg),
                                  params)
    want, want_grads = _loss_and_grads(
        lambda p: llama_loss(p, batch, dense), params)
    assert abs(float(loss) - float(want)) < TOL * float(want)
    assert _worst_leaf(grads, want_grads)[0] < GRAD_TOL
    # and the reference's dense form is the program's
    ref_dense, _ = _loss_and_grads(
        lambda p: ref.sala_loss(p, batch, dense), params)
    assert abs(float(ref_dense) - float(want)) < TOL * float(want)


@pytest.mark.parametrize("layer", [1, 31])
def test_the_lightning_mixer_is_ssd_and_the_recurrence(layer):
    """``ops/ssd.py`` called with ``x = v``, ``B = k``, ``C = q /
    sqrt(d)``, ``dt = 1``, ``A`` = the rates, no ``D``, a group a head
    equals the recurrence token by token; the rates are the published
    layer's (layer 31 of 32: hardly any decay, 1e-5 of a slope)."""
    b, t, H, d = 1, 128, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(layer), 3)
    q, k, v = (jax.random.normal(kk, (b, t, H, d), F32) for kk in ks)
    cfg = _cfg()
    rates = ref.sala_rates(cfg, layer)
    assert np.allclose(rates, -2.0 ** (-2.0 * (np.arange(4) + 1))
                       * (1 - layer / 31 + 1e-5), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda q, k, v: ssd(
            v, jnp.ones((b, t, H), F32), rates, k, q / d ** 0.5, None, 64))(
            q, k, v)
        want = ref.sala_recurrence(q, k, v, rates) / d ** 0.5
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) \
        < TOL


def test_the_programs_rates_follow_the_published_layer():
    """A stack's rows are its layers' places in ``layer_types`` among
    ``lightning_depth`` published layers, not their index in the stack."""
    cfg = _cfg((S, L, L, L))
    got = cfg.lightning_rates("lightning_layers")
    assert got.shape == (3, 4)
    for row, layer in zip(got, (1, 2, 3)):
        assert np.allclose(row, ref.sala_rates(cfg, layer), rtol=1e-6)


def test_a_decay_that_underflows_a_chunks_product_stays_finite():
    """Rates of -2 a token over a chunk of 64: exp(-128) is no float32,
    and every exponent the chunked form takes is a difference of running
    sums: outputs and gradients finite and the recurrence's."""
    b, t, H, d = 1, 128, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (b, t, H, d), F32) for kk in ks)
    rates = jnp.asarray([-2.0, -0.5], F32)

    def through(rule):
        def loss(q, k, v):
            return jnp.sum(jnp.square(rule(q, k, v)))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
                q, k, v)

    got = through(lambda q, k, v: ssd(v, jnp.ones((b, t, H), F32), rates,
                                      k, q, None, 64))
    want = through(lambda q, k, v: ref.sala_recurrence(q, k, v, rates))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < 1e-4


def test_one_adam_step_moves_the_loss_as_the_references():
    """``make_split_train_step`` on the program against one hand-written
    Adam step on the reference's gradients: the loss after the step."""
    import optax

    from horovod_tpu.parallel import make_split_train_step

    cfg, params, batch, (loss0, _), (_, want_grads) = _period()
    ts = make_split_train_step(lambda p, b: llama_loss(p, b, cfg),
                               optax.adam(1e-3))
    with jax.default_matmul_precision("highest"):
        loss, carry = ts.step(ts.init(jax.tree.map(jnp.copy, params)),
                              batch)
        after = jax.jit(lambda p: llama_loss(p, batch, cfg))(carry[0])
        # Adam's first step: lr * g / (|g| + eps)
        by_hand = jax.tree.map(
            lambda p, g: p - 1e-3 * g / (jnp.abs(g) + 1e-8), params,
            want_grads)
        want = jax.jit(lambda p: ref.sala_loss(p, batch, cfg))(by_hand)
    assert abs(float(loss) - float(loss0)) < TOL * float(loss0)
    assert float(after) < float(loss0)
    # the step is lr * sign(g) but where a gradient is within float32
    # noise of zero: a thousandth of the loss's move
    assert abs(float(after) - float(want)) \
        < 1e-3 * abs(float(loss0) - float(want))


def test_the_plan_the_leaves_and_their_partition_rules():
    import re

    cfg = _cfg()
    assert [(s.stack, s.index, s.mixer, s.dense_ffn, s.rope)
            for s in cfg.layer_plan()] == [
        ("sparse_layers", 0, "sparse", True, False),
        ("lightning_layers", 0, "lightning", True, False),
        ("lightning_layers", 1, "lightning", True, False),
        ("lightning_layers", 2, "lightning", True, False)]
    shapes = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    ffn = ["mlp_norm", "w_down", "w_gate", "w_up"]
    assert sorted(shapes["sparse_layers"]) == sorted(
        ["attn_norm", "wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm"]
        + ffn)
    assert sorted(shapes["lightning_layers"]) == sorted(
        ["attn_norm", "wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm",
         "out_norm"] + ffn)
    assert shapes["sparse_layers"]["wk"].shape == (1, 64, 32)
    assert shapes["lightning_layers"]["wk"].shape == (3, 64, 64)
    assert shapes["lightning_layers"]["out_norm"].shape == (3, 64)
    rules = llama_partition_rules()
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        name = "/".join(str(k.key) for k in path)
        spec = next(s for pat, s in rules if re.search(pat, name))
        assert len(spec) == leaf.ndim, (name, spec)


@pytest.mark.parametrize("field", [
    dict(layer_types=(S, S), **SELECTION),
    dict(layer_types=(L, L), **LIGHTNING),
    dict(embed_mult=12.0), dict(residual_mult=0.25), dict(logit_div=16.0),
    dict(ffn_chunk=64)],
    ids=["sparse", "lightning", "embed", "residual", "logits", "ffn-chunk"])
def test_decode_and_serving_refuse_the_new_fields(field):
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="training only"):
        gen.llama_prefill(params, jnp.zeros((1, 4), jnp.int32), cfg)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)


@pytest.mark.parametrize("case, match", [
    (dict(layer_types=(S, S)), "sparse_attention layers and"),
    (dict(sparse_block=16), "sparse_attention layers and"),
    (dict(layer_types=(L, L)), "lightning_attention layers and"),
    (dict(lightning_heads=4), "lightning_attention layers and"),
    (dict(ffn_chunk=-1), "ffn_chunk")])
def test_sizes_and_layers_come_together(case, match):
    with pytest.raises(ValueError, match=match):
        LlamaConfig.tiny(**case)


@pytest.mark.parametrize("axis", ["seq", "tensor"])
@pytest.mark.parametrize("kind", [S, L])
def test_the_mixers_refuse_a_split_mesh(kind, axis):
    cfg = _cfg((kind, kind))
    params = llama_init(cfg, jax.random.PRNGKey(0))
    shape = {"seq": (1, 1, 2, 1), "tensor": (1, 1, 1, 2)}[axis]
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:2]).reshape(shape),
        ("data", "fsdp", "seq", "tensor"))
    with pytest.raises(ValueError, match="runs whole on each device"):
        jax.eval_shape(lambda p: llama_loss(p, _batch(cfg), cfg, mesh),
                       params)


def test_a_configuration_without_the_new_fields_builds_the_old_tree():
    cfg = LlamaConfig.tiny(dtype="float32")
    assert not cfg.training_only_fields()
    assert sorted(llama_init(cfg, jax.random.PRNGKey(0))) == [
        "embed", "final_norm", "layers", "lm_head"]
