"""Ouro-2.6B (ouro: a looped decoder) through the normal llama path
against the plain float32 reference (horovod_tpu/models/reference.py,
whose trips and layers are Python loops): the loss and every gradient
leaf at four trips of ONE base configuration (two layers, tiny widths,
four norms a layer), the exit distribution by hand, the sum of a shared
leaf's gradient over its visits, one trip against the plain decoder,
and what the configuration, decode, serving and the pipeline refuse.
Small sizes, CPU; what is evaluated runs under ``jax.jit``.

Tolerance: float32 on both sides at "highest" matmul precision, every
op the same up to its order: 2e-5 of a leaf's l2 norm (read: 0.9-3.2e-6).
The same comparison with the program in bfloat16 has to fail.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models import generate as gen
from horovod_tpu.models.llama import (
    _exit_log_probs,
    _for_each_trip,
    llama_forward,
    llama_partition_rules,
)
from horovod_tpu.models.reference import (
    ouro_exit_distribution,
    ouro_forward,
    ouro_loss,
)

TOL = 2e-5
R = 4


def _cfg(**kw):
    """The cell's shape in small: two layers of four norms, as many
    key/value heads as heads, an untied head, four trips, beta 0.1."""
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=96, rope_theta=1e6, norm_eps=1e-6,
                post_norm=True, loop_steps=R, exit_entropy_weight=0.1,
                dtype="float32", param_dtype="float32", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _params(cfg, seed=0):
    """Seeded weights with every gain drawn away from 1 and the gate's
    bias away from 0, so that a norm left out, misplaced or applied
    outside the loop, or a bias dropped, moves the result."""
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))
    for name, w in params["layers"].items():
        if name.endswith("norm"):
            params["layers"][name] = jax.random.uniform(
                next(keys), w.shape, w.dtype, 0.5, 1.5)
    params["final_norm"] = jax.random.uniform(
        next(keys), params["final_norm"].shape, jnp.float32, 0.5, 1.5)
    if "exit_gate_b" in params:
        params["exit_gate_b"] = jnp.full((1,), 0.3, jnp.float32)
        params["exit_gate_w"] = params["exit_gate_w"] * 4.0
    return params


def _batch(cfg, shape=(2, 32), seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}


def _l2(got, ref):
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


@functools.lru_cache(maxsize=None)
def _reference_readings():
    """The reference's loss with its parts and its gradients, and the
    gradient of every VISIT apart (each trip reading a copy of the stack
    of its own): once for all cases."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    (loss, parts), grads = jax.jit(jax.value_and_grad(
        lambda p: ouro_loss(p, batch, cfg, terms=True),
        has_aux=True))(params)
    visits = jax.jit(jax.grad(lambda copies: ouro_loss(
        params, batch, cfg, trip_layers=copies)))(
        [params["layers"]] * R)
    return loss, parts, grads, visits


@functools.lru_cache(maxsize=None)
def _program_readings(**kw):
    cfg = _cfg(**kw)
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg)))(params)


def _leaf_errors(grads, ref):
    return {jax.tree_util.keystr(path): _l2(g, r) for (path, g), r in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(ref))}


def test_loss_and_every_gradient_leaf_at_four_trips():
    ref_loss, _, ref, _ = _reference_readings()
    loss, grads = _program_readings()
    assert abs(float(loss) - float(ref_loss)) < 1e-6 * float(ref_loss)
    errors = _leaf_errors(grads, ref)
    # the shared stack's 11 leaves, the gate's two, the embedding, the
    # final norm, the head
    assert len(errors) == 11 + 2 + 3 and "['exit_gate_b']" in errors
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(ref))
    assert max(errors.values()) < TOL, errors


def test_the_same_comparison_in_bfloat16_fails():
    _, _, ref, _ = _reference_readings()
    _, grads = _program_readings(dtype="bfloat16")
    errors = _leaf_errors(grads, ref)
    assert min(errors.values()) > 50 * TOL, errors


def test_the_cells_remat_mode_gives_the_gradients_of_remat_off():
    loss, grads = _program_readings()
    again, under = _program_readings(remat="attn")
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    assert max(_leaf_errors(under, grads).values()) < TOL


def test_a_shared_leafs_gradient_is_the_sum_of_its_four_visits():
    """Each visit reading its own copy of the stack, the reference gives
    four gradients a leaf; the shared leaf's is their sum, in the
    reference and in the program. No visit's share is small: a program
    that dropped a trip's would miss the sum by that share."""
    _, _, ref, visits = _reference_readings()
    _, grads = _program_readings()
    for name, whole in ref["layers"].items():
        terms = [v[name] for v in visits]
        assert _l2(sum(terms), whole) < TOL, name
        assert _l2(grads["layers"][name], sum(terms)) < TOL, name
        assert min(float(jnp.linalg.norm(t)) for t in terms) \
            > 0.02 * float(jnp.linalg.norm(whole)), name


def test_the_sum_over_trips_is_taken_in_float32_and_rounded_once():
    """The program's sum (``_for_each_trip``'s backward) on the four
    visits' gradients AS A bfloat16 PROGRAM HANDS THEM OVER, against the
    float32 sum of the same four, the matrices (thousands of entries a
    leaf; a gain's 128 spread wider): read 1.62e-3 - 1.69e-3 of a leaf's
    l2 norm, ONE rounding of the result to bfloat16 (8 bits of mantissa:
    2^-9 = 1.95e-3 at worst an entry, less in the mean by where the
    entries lie in their binades). A bfloat16 accumulator rounds three
    times (read 2.83e-3 - 2.95e-3, sqrt(3) times as much): the bound
    stands between."""
    _, _, _, visits = _reference_readings()
    handed = [jax.tree.map(lambda g: g.astype(jnp.bfloat16), v)
              for v in visits]

    @jax.jit
    def both(handed):
        _, vjp = jax.vjp(lambda s: _for_each_trip(s, R), handed[0])
        acc = handed[0]
        for v in handed[1:]:
            acc = jax.tree.map(lambda a, g: a + g, acc, v)
        return vjp(tuple(handed))[0], acc

    summed, accumulated = both(handed)
    exact = jax.tree.map(lambda *g: sum(x.astype(jnp.float32) for x in g),
                         *handed)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        once = _l2(summed[name], exact[name])
        thrice = _l2(accumulated[name], exact[name])
        assert summed[name].dtype == jnp.bfloat16
        assert once < 2.2e-3 < thrice, (name, once, thrice)


def test_one_trip_is_the_plain_decoder_of_four_norms():
    """``loop_steps`` 1 runs the program's old path (no gate leaf, no
    exit); the reference at one trip, where the one exit has all the
    mass and the entropy is zero, gives its loss: the reference's layer
    and closing norm are the old path's, and four trips of them are the
    new path's (above)."""
    cfg = _cfg(loop_steps=1, exit_entropy_weight=0.0)
    params, batch = _params(cfg), _batch(cfg)
    assert "exit_gate_w" not in params \
        and not set(cfg.training_only_fields()) & {"loop_steps",
                                                   "exit_entropy_weight"}
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p: llama_loss(p, batch, cfg))(params)
    gated = dict(params, exit_gate_w=jnp.ones(64), exit_gate_b=jnp.ones(1))
    ref, (nll, p, entropy) = jax.jit(
        lambda p: ouro_loss(p, batch, cfg, terms=True))(gated)
    assert float(loss) == pytest.approx(float(ref), rel=1e-6)
    assert p.tolist() == [1.0] and float(entropy) == 0.0 \
        and float(nll[0]) == pytest.approx(float(ref), rel=1e-6)


def test_the_forward_pass_gives_the_last_exits_logits():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: llama_forward(p, batch["tokens"], cfg))(
            params)
    ref, gates = jax.jit(lambda p: ouro_forward(p, batch["tokens"], cfg))(
        params)
    assert ref.shape == (R, 2, 32, 128) and gates.shape == (R, 2, 32)
    assert float(jnp.max(jnp.abs(got - ref[-1]))) \
        < 1e-4 * float(jnp.max(jnp.abs(ref)))
    # the exits differ: a trip does something
    assert float(jnp.max(jnp.abs(ref[0] - ref[-1]))) > 0.1


@pytest.mark.parametrize("trips", [2, 4])
def test_the_exit_distribution_by_hand(trips):
    """Sums to 1; at equal gates ``lambda (1 - lambda)^(t-1)`` and the
    last exit ``(1 - lambda)^(R-1)``; the last gate enters nothing; and
    the entropy's gradient pushes a skewed distribution towards the
    uniform one, where it vanishes."""
    s = jax.random.normal(jax.random.PRNGKey(trips), (trips, 5)) * 2.0
    p = jnp.exp(_exit_log_probs(s))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p, ouro_exit_distribution(s), rtol=1e-5)
    lam = 0.3
    equal = jnp.exp(_exit_log_probs(jnp.full((trips, 1),
                                             np.log(lam / (1 - lam)))))
    by_hand = [lam * (1 - lam) ** t for t in range(trips - 1)] \
        + [(1 - lam) ** (trips - 1)]
    np.testing.assert_allclose(equal[:, 0], by_hand, rtol=1e-5)
    np.testing.assert_array_equal(
        p, jnp.exp(_exit_log_probs(s.at[-1].set(9.0))))

    def entropy(s):
        log_p = _exit_log_probs(s)
        return -jnp.sum(jnp.exp(log_p) * log_p)

    # uniform over R exits: lambda_t = 1 / (R - t + 1)
    uniform = jnp.log(1.0 / jnp.arange(trips - 1, 0, -1.0))[:, None]
    uniform = jnp.concatenate([uniform, jnp.zeros((1, 1))])
    np.testing.assert_allclose(jnp.exp(_exit_log_probs(uniform)),
                               1.0 / trips, rtol=1e-5)
    assert float(jnp.max(jnp.abs(jax.grad(entropy)(uniform)))) < 1e-6
    skewed = uniform.at[0].add(2.0)      # most of the mass leaves first
    step = skewed + 0.5 * jax.grad(entropy)(skewed)
    assert float(entropy(step)) > float(entropy(skewed))
    assert abs(float(step[0, 0] - uniform[0, 0])) \
        < abs(float(skewed[0, 0] - uniform[0, 0]))


def test_the_gate_is_learned_through_both_terms_and_nothing_is_detached():
    """The reference's parts add up to its loss; the gate's gradient has
    a part from the expected loss and one from the entropy (``beta`` 0
    moves it), and an exit's hidden gets gradient through the gate that
    reads it (a gate's weight scaled moves the embedding's gradient)."""
    ref_loss, (nll, p, entropy), ref, _ = _reference_readings()
    assert float(jnp.sum(p)) == pytest.approx(1.0, rel=1e-5)
    assert nll.shape == p.shape == (R,) and float(entropy) > 0.5
    _, no_entropy = _program_readings(exit_entropy_weight=0.0)
    assert _l2(no_entropy["exit_gate_w"], ref["exit_gate_w"]) > 0.05
    assert _l2(no_entropy["embed"], ref["embed"]) > 1e-3


def test_the_tree_has_the_gates_two_leaves_and_a_rule_each():
    import re

    cfg = _cfg()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "exit_gate_b", "exit_gate_w",
                              "final_norm", "layers", "lm_head"]
    assert params["exit_gate_w"].shape == (64,) \
        and params["exit_gate_b"].shape == (1,)
    assert sorted(k for k in params["layers"] if k.endswith("norm")) == [
        "attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm"]
    rules = llama_partition_rules()
    for name in ("exit_gate_w", "exit_gate_b"):
        spec = next(spec for pattern, spec in rules
                    if re.search(pattern, name))
        assert len(spec) == params[name].ndim
    # an unlooped model's weights do not move for the new leaves' keys
    plain = llama_init(dataclasses.replace(
        cfg, loop_steps=1, exit_entropy_weight=0.0), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(plain["layers"]["wq"],
                                  params["layers"]["wq"])
    np.testing.assert_array_equal(plain["lm_head"], params["lm_head"])


@pytest.mark.parametrize("bad", [
    dict(loop_steps=0), dict(loop_steps=1, exit_entropy_weight=0.1),
    dict(n_experts=4), dict(mtp_layers=1, mtp_types=("full_attention",),
                            mtp_weight=0.3)])
def test_what_the_configuration_refuses(bad):
    with pytest.raises(ValueError, match="loop_steps"):
        _cfg(**bad)


def test_decode_serving_and_the_pipeline_refuse_the_loop_by_name():
    from horovod_tpu.models.llama import _validate_pipeline
    from horovod_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype="float32", loop_steps=2,
                           exit_entropy_weight=0.1)
    assert set(cfg.training_only_fields()) == {"loop_steps",
                                               "exit_entropy_weight"}
    params = llama_init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="loop_steps.*training only"):
        gen.llama_prefill(params, prompt, cfg)
    with pytest.raises(ValueError, match="loop_steps.*training only"):
        DecodeEngine(params, cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pipe",))
    with pytest.raises(ValueError, match="loop_steps.*circular"):
        _validate_pipeline(cfg, 2, mesh, "seq", 2)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "targets": jnp.zeros((2, 8), jnp.int32)}
    with pytest.raises(ValueError, match="loop_steps"):
        llama_loss(params, batch, cfg, mesh)
