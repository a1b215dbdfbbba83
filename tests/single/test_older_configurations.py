"""What the configurations the benchmark already had build and lower to,
pinned in ONE place: a row a configuration, a case a row and a property.
A ``model_config`` PR that adds fields to ``LlamaConfig`` checks here
that the older models did not move; one that changes them on purpose
reads the row again and says so beside it. It adds a ROW for its own
configuration, not a copy of this table in its own test file (the three
copies this file replaces stood in test_afmoe_reference.py,
test_lfm2_reference.py and test_qwen3next_reference.py).

The properties:

``program``  the layer stack's leaves by name, the loss on seeded
             weights to the last digit, and the count of the operations
             in the jaxpr of the grad program;
``built``    the parameter tree's stacks, every mixer an attention
             layer, the same loss, and where RoPE is applied;
``text``     the StableHLO text of the grad program at abstract
             operands, length and digest, to the last byte.

The loss is read by the EAGER call of ``llama_loss``, a primitive at a
time as a user without ``jax.jit`` runs it: once a configuration a
module (``built``), whichever property asks first.

How the pinned values were read. "dense" and "olmoe" under ``program``:
counted at commit a7fcac2, where the whole jaxpr texts were compared
once, equal but for the address of a remat policy's closure; "olmoe"
again at PR 33, which runs a grouped expert stack unrolled on purpose
(no scan, a layer body a layer: the printed jaxpr shares equal
sub-programs, so its counts are not twice a body's; the loss differs
from the scan's in the fourth digit at bf16 compute and agrees to 1e-6
in float32, test_expert_stack_unrolled.py), and at PR 54, which took
``_top_k``'s scatter-add out of the router on purpose (a select under a
sum, ``models/llama.py:_unpick``: one scatter-add fewer, the loss the
same to the last digit). The losses under ``built``: read at commit
c001471, after the stacks went by kind of layer. ``text``: read at
commit 599fcd4 by this very code; the two share models again at PR 41,
which changed them on purpose (two chunks a layer here: the second is a
loop that follows the rows held, ``grouped_moe._later_chunks``); the
three with a router again at PR 54 (the pick of the K chosen scores and
its transpose as selects under a sum, ``models/llama.py:_pick``;
"dense-attn" stands as it was read).
"""

import collections
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss

S, F, C = "sliding_attention", "full_attention", "conv"
_LEAVES = ["attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
           "wq", "wv"]
_OLMOE = LlamaConfig.tiny(n_experts=8, n_experts_per_token=3, qk_norm=True,
                          norm_topk_prob=False, moe_impl="grouped",
                          remat="attn+moe")
_TRINITY = dict(vocab_size=128, d_model=64, n_layers=5, n_heads=4,
                n_kv_heads=2, d_head=32, d_ff=96, moe_d_ff=32,
                rope_theta=10000.0, n_experts=16, n_experts_per_token=4,
                n_dense_layers=1, layer_types=(S, S, S, S, F),
                sliding_window=6, n_shared_experts=1,
                score_func="sigmoid", norm_topk_prob=True,
                route_scale=2.826, scale_embed=True, attn_gate=True,
                post_norm=True, qk_norm="head", first_expert=4,
                n_experts_held=4, moe_impl="grouped", moe_aux_weight=0.0,
                dtype="float32", param_dtype="float32", remat=False)
_LFM2 = dict(vocab_size=128, d_model=64, n_layers=9, n_heads=4,
             n_kv_heads=2, d_ff=96, moe_d_ff=32, rope_theta=1e6,
             n_experts=8, n_experts_per_token=4, n_dense_layers=1,
             layer_types=(C,) + (F, C, C, C) * 2, conv_taps=3,
             rope_full_attention=True, tie_embeddings=True,
             score_func="sigmoid", norm_topk_prob=True, route_scale=1.0,
             qk_norm="head", first_expert=2, n_experts_held=2,
             moe_impl="grouped", moe_aux_weight=0.0, dtype="bfloat16",
             param_dtype="float32", remat="attn")

# configuration -> its LlamaConfig and what is pinned of it: ``stacks``
# and ``loss`` serve ``built``, ``leaves``, ``loss`` and ``counts``
# serve ``program``, ``text`` is (digest, length). A row has a case for
# each property it holds the values of.
ROWS = {
    "dense": dict(
        cfg=LlamaConfig.tiny(), stacks=["layers"], leaves=_LEAVES,
        loss=5.90579891204834,
        counts={"scan": 2, "cond": 0, "sort": 0, "gather": 2,
                "scatter-add": 2, "custom_vjp_call": 0, "dot_general": 38,
                "top_k": 0}),
    "dense-attn": dict(
        cfg=LlamaConfig.tiny(remat="attn"),
        text=("b353182b28726edf", 93403)),
    "olmoe": dict(
        cfg=_OLMOE, stacks=["layers"],
        leaves=["attn_norm", "k_norm", "mlp_norm", "moe_down", "moe_gate",
                "moe_up", "q_norm", "router", "wk", "wo", "wq", "wv"],
        loss=6.124673366546631,
        counts={"scan": 0, "cond": 0, "sort": 3, "gather": 14,
                "scatter-add": 2, "custom_vjp_call": 10, "dot_general": 99,
                "top_k": 2},
        text=("19d57556ecb10f1a", 234792)),
    "trinity": dict(
        cfg=LlamaConfig(**_TRINITY), stacks=["dense_layers", "layers"],
        loss=5.229442119598389),
    "trinity-bf16": dict(
        cfg=LlamaConfig(**dict(_TRINITY, dtype="bfloat16", remat="attn")),
        stacks=["dense_layers", "layers"], loss=5.231811046600342,
        text=("8161ae6d2fb7892f", 1018007)),
    "lfm2": dict(cfg=LlamaConfig(**_LFM2),
                 text=("20551dd593a4c1e1", 1246731)),
    # PR 64's own row: four trips of ONE stack of four-norm layers under
    # the cell's remat mode and a blocked head, read at PR 64.
    "ouro": dict(
        cfg=LlamaConfig.tiny(n_kv_heads=4, post_norm=True, norm_eps=1e-6,
                             loop_steps=4, exit_entropy_weight=0.1,
                             loss_chunk=16, remat="attn"),
        text=("67ffbf4aaaefdde5", 423326)),
}


@pytest.fixture(scope="module")
def built():
    """-> ``which -> (params, batch, the eager loss)``, each
    configuration initialised and run once a module."""
    made = {}

    def build(which):
        if which not in made:
            cfg = ROWS[which]["cfg"]
            params = llama_init(cfg, jax.random.PRNGKey(0))
            tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                        min(cfg.vocab_size, 256))
            batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
            made[which] = params, batch, float(llama_loss(params, batch,
                                                          cfg))
        return made[which]

    return build


def _program(which, built):
    row, cfg = ROWS[which], ROWS[which]["cfg"]
    params, batch, loss = built(which)
    assert sorted(params) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(params["layers"]) == row["leaves"]
    assert params["layers"]["wq"].shape == (2, 64, 64)
    if which == "olmoe":
        assert params["layers"]["q_norm"].shape == (2, 64)
        assert params["layers"]["moe_gate"].shape == (2, 8, 64, 128)
    assert loss == row["loss"]
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, b: llama_loss(p, b, cfg)))(params, batch))
    seen = collections.Counter(re.findall(r"= ([a-z_\-]+)[\[ ]", text))
    assert {k: seen[k] for k in row["counts"]} == row["counts"]


def _built(which, built):
    row, cfg = ROWS[which], ROWS[which]["cfg"]
    params, _, loss = built(which)
    assert sorted(params) == sorted(row["stacks"] + ["embed", "final_norm",
                                                     "lm_head"])
    assert all(s.mixer == "attention" for s in cfg.layer_plan())
    assert loss == row["loss"]
    if "trinity" in which:   # full_attention without RoPE, as ever
        assert [s.rope for s in cfg.layer_plan()] == [True] * 4 + [False]


def _text(which, built):
    """The new fields at their defaults: none counts as set, none makes
    a leaf, and the gradient program's text is the one read."""
    cfg = ROWS[which]["cfg"]
    assert not set(cfg.training_only_fields()) & {
        "linear_key_heads", "linear_value_heads", "linear_key_dim",
        "linear_value_dim", "partial_rotary", "shared_expert_gate"}
    assert which == "ouro" or not set(cfg.training_only_fields()) & {
        "loop_steps", "exit_entropy_weight"}
    params = jax.eval_shape(lambda k: llama_init(cfg, k),
                            jax.random.PRNGKey(0))
    assert not [k for stack in params.values() if isinstance(stack, dict)
                for k in stack if k.startswith(("gdn_", "shared_score"))]
    assert which == "ouro" or not [k for k in params
                                   if k.startswith("exit_gate")]
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(jax.value_and_grad(lambda p, t: llama_loss(
        p, {"tokens": t, "targets": t}, cfg))).lower(params,
                                                      tokens).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()[:16], len(text)) \
        == ROWS[which]["text"]


# property -> (the key of ``ROWS`` that holds its pinned values, its check)
_PROPERTIES = {"program": ("counts", _program), "built": ("stacks", _built),
               "text": ("text", _text)}
CASES = [(which, prop) for which, row in ROWS.items()
         for prop, (key, _) in _PROPERTIES.items() if key in row]


@pytest.mark.parametrize("which,prop", CASES,
                         ids=[f"{w}-{p}" for w, p in CASES])
def test_an_older_configuration_is_what_it_was(which, prop, built):
    _PROPERTIES[prop][1](which, built)
