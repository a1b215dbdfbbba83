"""AI21-Jamba2-3B's layers through the program's normal path
(``LlamaConfig`` -> ``llama_init`` -> ``llama_loss``) against the plain
float32 reference (``horovod_tpu/models/reference.py: jamba_*``) on
seeded weights, at small sizes on the CPU: the mamba mixer alone, one
mamba layer and one attention layer at twenty heads on one, a 14-layer
model's loss and every gradient leaf; the head and loss in token blocks
against whole logits; the new stack under the partition rules; what the
configuration, decode, serving and the pipeline refuse; and that a
configuration that sets none of the new fields builds the tree it always
did.

Float32 compute: program and reference then differ in the order of
float32 additions (the scan's chunks, the blocks of the head): 2e-5 of
the largest entry, 5e-5 (l2) of a gradient leaf through fourteen
layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
)
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import _mamba, llama_partition_rules
from horovod_tpu.models.reference import (
    _rms,
    jamba_forward,
    jamba_loss,
    jamba_mamba_mixer,
)
from horovod_tpu.ops import selective_scan as scan_module

pytestmark = pytest.mark.quick
F32 = jnp.float32
M, A = "mamba", "full_attention"
TOL, GRAD_TOL = 2e-5, 5e-5
MAMBA = dict(conv_taps=4, mamba_d_state=16, mamba_dt_rank=8,
             mamba_expand=2, mamba_conv_bias=True)


def _cfg(layer_types=None, **kw):
    """The cell's shape in small: one period, layer ``i`` attention
    where ``i % 14 == 7``; 128 channels of 16 states; four heads on one;
    a tied head."""
    types = layer_types or tuple(A if i % 14 == 7 else M
                                 for i in range(14))
    base = dict(vocab_size=128, d_model=64, n_layers=len(types), n_heads=4,
                n_kv_heads=1, d_head=16, d_ff=96, norm_eps=1e-6,
                layer_types=types, tie_embeddings=True, dtype="float32",
                remat="attn/ffn", **(MAMBA if M in types else {}))
    base.update(kw)
    return LlamaConfig.tiny(**base)


def _params(cfg, seed=0):
    """Seeded weights with every gain, bias and ``D`` moved off its
    start, so that each enters the comparison."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        w if w.shape[0] == cfg.vocab_size or w.shape[-1] == cfg.vocab_size
        else w + 0.1 * jax.random.normal(k, w.shape, w.dtype)
        for w, k in zip(leaves, keys)])


def _batch(cfg, shape=(2, 96), seed=3):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _rel(got, ref):
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _worst_leaf(got, ref):
    errs = jax.tree.map(lambda g, r: float(jnp.linalg.norm(g - r)
                                           / jnp.linalg.norm(r)), got, ref)
    flat = jax.tree_util.tree_flatten_with_path(errs)[0]
    return max((e, jax.tree_util.keystr(path)) for path, e in flat)


def test_the_mixer_is_the_references():
    cfg = _cfg((M,))
    lp = jax.tree.map(lambda w: w[0], _params(cfg)["mamba_layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    got = jax.jit(lambda x, lp: _mamba(x, lp, cfg, None, None))(x, lp)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x, lp: jamba_mamba_mixer(
            _rms(x, lp["ssm_norm"], cfg.norm_eps), lp, cfg))(x, lp)
    assert _rel(got, ref) < TOL


@pytest.mark.parametrize("kind,heads", [(M, (4, 1)), (A, (20, 1))])
def test_one_layer_of_either_kind(kind, heads):
    """A one-layer model is a uniform stack: the layer under
    ``lax.scan``. Attention at twenty heads on one, 8 wide."""
    cfg = _cfg((kind,), d_model=160 if kind == A else 64,
               n_heads=heads[0], n_kv_heads=heads[1],
               d_head=8 if kind == A else 16)
    params, batch = _params(cfg), _batch(cfg, (2, 48))
    got = jax.jit(lambda p: llama_forward(p, batch["tokens"], cfg))(params)
    ref = jax.jit(lambda p: jamba_forward(p, batch["tokens"], cfg))(params)
    assert _rel(got, ref) < TOL


@pytest.mark.parametrize("layers,kernels", [(14, True), (3, False)])
def test_loss_and_every_gradient_leaf(layers, kernels, monkeypatch):
    """The whole pattern of fourteen layers on the kernel pair
    (interpret mode), and three layers (mamba, attention, mamba) on the
    scan: the stacks by kind, the tied head in blocks of 64 tokens,
    under remat "attn/ffn"."""
    monkeypatch.setattr(scan_module, "_INTERPRET", kernels)
    cfg = _cfg(None if layers == 14 else (M, A, M), loss_chunk=64)
    assert [s.mixer for s in cfg.layer_plan()].count("mamba") == layers - 1
    params, batch = _params(cfg), _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg)))(params)
    ref, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: jamba_loss(p, batch, cfg)))(params)
    assert abs(float(loss) - float(ref)) < 1e-5 * float(ref)
    worst = _worst_leaf(grads, ref_grads)
    assert worst[0] < GRAD_TOL, worst


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("masked", [False, True])
def test_the_head_in_token_blocks_is_the_head(tied, masked):
    """``loss_chunk`` changes no value: the loss and every gradient
    leaf, the tied matrix's (the blocks' sum plus the lookup's)
    included, against whole logits; 192 tokens in blocks of 48 (the
    largest divisor under 50), with a mask and without."""
    whole = _cfg((M, A), tie_embeddings=tied, vocab_size=160)
    blocks = _cfg((M, A), tie_embeddings=tied, vocab_size=160,
                  loss_chunk=50)
    params, batch = _params(whole), _batch(whole)
    assert ("lm_head" in params) != tied
    if masked:
        batch["mask"] = jax.random.bernoulli(jax.random.PRNGKey(9), 0.7,
                                             batch["tokens"].shape)
    text = jax.jit(lambda p: llama_loss(p, batch, blocks)).lower(
        params).as_text()
    assert "tensor<48x160xf32>" in text
    assert "x96x160xf32" not in text and "<192x160xf32" not in text
    got, ref = (jax.jit(jax.value_and_grad(
        lambda p, c=c: llama_loss(p, batch, c)))(params)
        for c in (blocks, whole))
    assert abs(float(got[0]) - float(ref[0])) < 1e-6 * float(ref[0])
    worst = _worst_leaf(got[1], ref[1])
    assert worst[0] < 1e-5, worst
    # llama_forward returns whole logits whatever loss_chunk says
    assert llama_forward(params, batch["tokens"], blocks).shape \
        == (2, 96, 160)


def test_the_mamba_stack_under_layer_plan_and_the_partition_rules():
    import re

    cfg = _cfg()
    plan = cfg.layer_plan()
    # a RUN of mamba layers is a stack: each is scanned whole
    assert [(s.stack, s.index) for s in plan] == [
        ("mamba_layers", i) for i in range(7)] + [("layers", 0)] + [
        ("mamba_1_layers", i) for i in range(6)]
    assert all(s.dense_ffn and not s.window and not s.rope for s in plan)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "final_norm", "layers",
                              "mamba_1_layers", "mamba_layers"]
    assert {k: v.shape[1:] for k, v in params["mamba_1_layers"].items()} \
        == {k: v.shape[1:] for k, v in params["mamba_layers"].items()}
    assert params["mamba_1_layers"]["ssm_in"].shape[0] == 6
    assert float(jnp.max(jnp.abs(params["mamba_1_layers"]["ssm_in"][0]
                                 - params["mamba_layers"]["ssm_in"][0]))) > 0
    tokens = jnp.zeros((1, 8), jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda p: llama_forward(p, tokens, cfg))(
        params))
    assert jaxpr.count("scan[") >= 2 and jaxpr.count("ssm_scan") == 0
    stack = params["mamba_layers"]
    assert {k: v.shape[1:] for k, v in stack.items()
            if k.startswith("ssm_")} == {
        "ssm_norm": (64,), "ssm_in": (64, 256), "ssm_conv": (4, 128),
        "ssm_conv_bias": (128,), "ssm_x": (128, 8 + 32),
        "ssm_dt_norm": (8,), "ssm_b_norm": (16,), "ssm_c_norm": (16,),
        "ssm_dt": (8, 128), "ssm_dt_bias": (128,),
        "ssm_a_log": (128, 16), "ssm_d": (128,), "ssm_out": (128, 64)}
    assert all(v.shape[0] == 7 for v in stack.values())
    assert not any(k.startswith("ssm_") for k in params["layers"])
    # the published start: A[c, n] = -(n + 1), D = 1, steps inside
    # (1e-3, 0.1), the convolution's bias 0
    assert np.allclose(np.exp(stack["ssm_a_log"][3, 5]),
                       np.arange(1, 17), rtol=1e-6)
    steps = np.asarray(jax.nn.softplus(stack["ssm_dt_bias"]))
    assert 1e-3 <= steps.min() < 3e-3 and 0.03 < steps.max() <= 0.1
    assert float(jnp.max(jnp.abs(stack["ssm_conv_bias"]))) == 0.0
    assert float(jnp.min(stack["ssm_d"])) == 1.0
    # every leaf of the new stack meets a rule, none a tensor axis
    rules = llama_partition_rules()
    for name, leaf in stack.items():
        spec = next(spec for pattern, spec in rules
                    if re.search(pattern, "mamba_layers/" + name))
        assert len(spec) == leaf.ndim, name
        if name.startswith("ssm_"):
            assert "tensor" not in jax.tree.leaves(tuple(spec)), name
    # without the bias key the tree has no such leaf
    bare = llama_init(_cfg(mamba_conv_bias=False), jax.random.PRNGKey(0))
    assert "ssm_conv_bias" not in bare["mamba_layers"]


@pytest.mark.parametrize("bad,why", [
    (dict(mamba_d_state=0), "three sizes"),
    (dict(mamba_dt_rank=0), "three sizes"),
    (dict(conv_taps=0), "conv_taps come together"),
    (dict(layer_types=(A,) * 14, mamba_d_state=16), "three sizes"),
    (dict(layer_types=(A,) * 14, mamba_conv_bias=True), "three sizes"),
    (dict(loss_chunk=-1), "tokens a block")])
def test_what_the_configuration_refuses(bad, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**bad)


def test_the_mixer_refuses_a_split_mesh():
    cfg = _cfg((M, A))
    params = _params(cfg)
    for split in ((2, 1), (1, 2)):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:2]).reshape(1, 1, *split),
            ("data", "fsdp", "seq", "tensor"))
        with pytest.raises(ValueError, match="runs whole on each device"):
            jax.eval_shape(lambda p: llama_loss(
                p, _batch(cfg, (2, 64)), cfg, mesh), params)


@pytest.mark.parametrize("field", [
    dict(layer_types=(M, M), **MAMBA), dict(loss_chunk=16)])
def test_decode_serving_and_the_pipeline_refuse_the_new_fields(field):
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", **field)
    assert set(cfg.training_only_fields()) >= set(field)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="training only"):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="training only"):
        DecodeEngine(params, cfg)
    if "layer_types" in field:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
        with pytest.raises(ValueError, match="mamba layers"):
            _validate_pipeline(cfg, 2, mesh, "seq", 2)


def test_a_configuration_without_the_new_fields_builds_what_it_built():
    """None of the new fields set: no new leaf, no new stack, and the
    fields do not count as set."""
    for cfg in (LlamaConfig.tiny(), LlamaConfig.tiny_moe(),
                LlamaConfig.tiny(tie_embeddings=True, conv_taps=3,
                                 layer_types=("conv", "full_attention"))):
        assert not set(cfg.training_only_fields()) & {
            "mamba_d_state", "mamba_dt_rank", "mamba_expand",
            "mamba_conv_bias", "loss_chunk"}
        shapes = jax.eval_shape(lambda k, cfg=cfg: llama_init(cfg, k),
                                jax.random.PRNGKey(0))
        assert "mamba_layers" not in shapes
        assert not [k for stack in shapes.values()
                    if isinstance(stack, dict) for k in stack
                    if k.startswith("ssm_")]
    dense = jax.eval_shape(lambda k: llama_init(LlamaConfig.tiny(), k),
                           jax.random.PRNGKey(0))
    assert sorted(dense) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(dense["layers"]) == [
        "attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
        "wq", "wv"]


def test_a_full_device_passes_one_set_of_gradient_buffers_round(monkeypatch):
    """Where the device has no room for a second step's gradients
    (``holds_two_gradients``: here told so), the split step's grad
    program takes the buffers the apply has just read, donated, and
    writes into them: the same losses and parameters as the plain step,
    three steps long, and the program's outputs alias its third
    argument."""
    import optax

    from horovod_tpu.parallel import make_split_train_step, train_step

    cfg = _cfg((M, A), loss_chunk=64)
    params, batch = _params(cfg), _batch(cfg, (2, 64))

    def loss_fn(p, d):
        return llama_loss(p, d, cfg)

    def three_steps():
        ts = make_split_train_step(loss_fn, optax.adam(1e-2))
        carry, losses = ts.init(jax.tree.map(jnp.copy, params)), []
        for _ in range(3):
            loss, carry = ts.step(carry, batch)
            losses.append(float(loss))
        return losses, carry[0]

    assert train_step.holds_two_gradients(params, ())     # the CPU
    plain = three_steps()
    monkeypatch.setattr(train_step, "holds_two_gradients",
                        lambda params, opt: False)
    round_ = three_steps()
    assert plain[0] == round_[0] and plain[0][2] < plain[0][0]
    assert _worst_leaf(round_[1], plain[1])[0] == 0.0
    # ONE set of buffers went round, and is left for whoever steps next
    (left,) = train_step._SPARE_GRADIENTS.values()
    assert jax.tree.structure(left) == jax.tree.structure(params)
    taken = train_step.spare_gradients(params)
    assert taken is left and not train_step._SPARE_GRADIENTS
    text = train_step.grad_program(loss_fn, True, {}).lower(
        params, batch, params).as_text()
    assert text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor") == len(jax.tree.leaves(params))
