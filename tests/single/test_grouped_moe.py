"""Dropless grouped-GEMM MoE dispatch (ops/grouped_moe.py) vs the
GShard one-hot path (models/llama.py:_moe_ffn).

When no token exceeds GShard capacity the two are the same function
(same router, gate normalization, aux loss) computed two ways — values
AND gradients must agree. When tokens overflow, GShard drops them on
the residual and grouped (dropless) computes them — a semantic
difference these tests pin on purpose.

The CPU substrate drives grouped_moe's exact one-hot fallback for the
grouped matmul; the megablox kernel itself is bench/TPU-only (its
interpret mode cannot differentiate).
"""

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import LlamaConfig, llama_init, llama_loss
from horovod_tpu.models.llama import _moe_ffn, moe_balance_loss
from horovod_tpu.ops.grouped_moe import grouped_moe_ffn


def _layer0(cfg, key=0):
    params = llama_init(cfg, jax.random.PRNGKey(key))
    return jax.tree.map(lambda x: x[0], params["layers"])


def _h(cfg, B=2, T=16, key=3):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (B, T, cfg.d_model), jnp.float32)


def _dropless_cfg(**kw):
    # capacity_factor = E makes per-group capacity C = T*K — no routing
    # pattern can overflow it, so GShard provably drops nothing and the
    # two dispatches compute the same math.
    kw.setdefault("capacity_factor", float(kw.get("n_experts", 4)))
    return LlamaConfig.tiny_moe(dtype="float32", remat=False, **kw)


def test_grouped_moe_matches_gshard_when_dropless():
    cfg = _dropless_cfg()
    lp = _layer0(cfg)
    h = _h(cfg)
    y_ref, aux_ref = _moe_ffn(h, lp, cfg, None)
    y, aux = grouped_moe_ffn(h, lp, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(aux_ref),
                               rtol=1e-6)


def test_grouped_moe_gradients_match_gshard():
    cfg = _dropless_cfg()
    lp = _layer0(cfg)
    h = _h(cfg)

    def loss(fn, h, lp):
        y, aux = fn(h, lp)
        return ((y.astype(jnp.float32) ** 2).mean()
                + 0.01 * moe_balance_loss(aux))

    g_ref = jax.grad(lambda h, lp: loss(
        lambda a, b: _moe_ffn(a, b, cfg, None), h, lp), (0, 1))(h, lp)
    g = jax.grad(lambda h, lp: loss(
        lambda a, b: grouped_moe_ffn(a, b, cfg), h, lp), (0, 1))(h, lp)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g_ref[0]),
                               rtol=2e-5, atol=2e-6, err_msg="dh")
    for name in g[1]:
        np.testing.assert_allclose(
            np.asarray(g[1][name]), np.asarray(g_ref[1][name]),
            rtol=2e-5, atol=2e-6, err_msg=f"d{name}")


def test_grouped_moe_is_dropless_where_gshard_drops():
    # Tiny capacity forces GShard to drop most overflow tokens; the
    # grouped path must still compute every (token, k) slot.
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False,
                               capacity_factor=0.25)
    lp = _layer0(cfg)
    # Bias the router hard toward expert 0 so overflow is guaranteed.
    lp = dict(lp)
    lp["router"] = lp["router"].at[:, 0].add(10.0)
    h = _h(cfg)
    y_gshard, _ = _moe_ffn(h, lp, cfg, None)
    y_grouped, _ = grouped_moe_ffn(h, lp, cfg)
    # GShard zeroes dropped slots (falls through on the residual);
    # grouped computes them, so some tokens must differ materially.
    diff = np.abs(np.asarray(y_grouped) - np.asarray(y_gshard)).max(-1)
    assert (diff > 1e-3).any(), "expected dropped tokens to differ"
    # And every grouped token got SOME expert output (dropless).
    assert (np.abs(np.asarray(y_grouped)).max(-1) > 1e-6).all()


def test_llama_forward_grouped_impl_end_to_end():
    # moe_impl="auto" with no mesh resolves to the grouped path; the
    # full forward + loss must be finite and trainable.
    cfg = LlamaConfig.tiny_moe(dtype="float32", remat=False)
    assert cfg.moe_impl == "auto"
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    loss, grads = jax.value_and_grad(llama_loss)(params, batch, cfg)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # The expert weights receive gradient (routing engaged).
    assert float(jnp.abs(grads["layers"]["moe_down"]).max()) > 0


def test_bwd_tilings_clamp_per_direction():
    """Each backward matmul's tiling clamps against ITS OWN problem
    dims, not the forward's (ADVICE r5): the dlhs gmm (transpose_rhs)
    reads its (m, contraction, out) as (m, n, k) — contraction over the
    forward's OUTPUT dim n, output over the forward's contraction k —
    while tgmm's dims coincide with the forward's (m, k, n)."""
    from horovod_tpu.ops.grouped_moe import _bwd_tilings

    # d_model(k)=512 < 1024 <= d_ff(n)=2048 — the straddling shape that
    # mis-clamped before: the old forward-dims clamp gave dlhs a
    # contraction tile of 512 (under its real 2048) and an output tile
    # of 1024 (OVER its real 512-wide output).
    dlhs, tgmm = _bwd_tilings(4096, 512, 2048)
    assert dlhs == (512, 1024, 512), dlhs   # (m, n=2048->1024, k=512)
    assert tgmm == (512, 512, 1024), tgmm   # (m, k=512, n=2048->1024)

    # Small-everything shapes clamp every direction to the problem.
    dlhs, tgmm = _bwd_tilings(256, 128, 64)
    assert dlhs == (256, 64, 128), dlhs
    assert tgmm == (256, 128, 64), tgmm

    # Large square shapes sit at the swept optimum in all directions.
    dlhs, tgmm = _bwd_tilings(16384, 2048, 4096)
    assert dlhs == (512, 1024, 1024), dlhs
    assert tgmm == (512, 1024, 1024), tgmm
