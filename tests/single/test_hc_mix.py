"""A part's stream mixing under hyper-connections as two kernel pairs
(``horovod_tpu/ops/hc_mix.py``), in pallas interpret mode on the CPU,
against the expressions of ``models/llama.py`` that run off the TPU:
``u``, the 24 coefficients and ``X'`` forward; every gradient through
BOTH pairs with a stand-in part between them (the seam between the pairs
is a derivative only so); the clamp's two edges; that the iterations'
number and ``H_post``'s scale are read when the program is traced; where
``on_kernels`` says no; and Xing4.0's layers whole against the plain
reference with the kernels interpreted.

Whole lane slabs (``D`` 256 and 384) and two tiles of 128 tokens, as the
chip takes them; only the model test shrinks a slab (its ``d_model`` is
32)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama, llama_loss
from horovod_tpu.ops import hc_mix as module
from tests.single import test_xing4_reference as xing4

pytestmark = pytest.mark.quick
F32, BF16 = jnp.float32, jnp.bfloat16
N, T = 4, 256


@dataclasses.dataclass(frozen=True)
class _Sizes:
    """What ``_hyper_connection`` reads of a configuration."""
    hc_mult: int = N
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    norm_eps: float = 1e-6


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setattr(module, "_INTERPRET", True)


def _operands(dtype, B, D, seed=0, logit_shift=None):
    """Streams, a part's three leaves (logits of ``H_res`` several units
    apart, as the seed's ``2 I`` plus noise under an ``alpha`` of 4: the
    twentieth iteration then shows), the stand-in part's weight and the
    weights of the loss."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    X = jax.random.normal(ks[0], (B, N, T, D), F32).astype(dtype)
    phi = 0.05 * jax.random.normal(ks[1], (N, D, N * (N + 2)), F32)
    alpha = jnp.array([0.7, 1.3, 4.0], F32)
    bias = 0.3 * jax.random.normal(ks[2], (N * (N + 2),), F32) \
        + jnp.concatenate([jnp.zeros(2 * N), 2.0 * jnp.eye(N).ravel()])
    if logit_shift is not None:
        bias = bias.at[2 * N:].add(jnp.asarray(logit_shift, F32))
    w = (jax.random.normal(ks[3], (D, D), F32) / np.sqrt(D)).astype(dtype)
    weights = jax.random.normal(ks[4], (B, N, T, D), F32)
    return X, phi, alpha, bias, w, weights


def _mixed(X, phi, alpha, bias, w, c=_Sizes()):
    """One part round the streams, the part a stand-in that reads ``u``
    and has a weight of its own."""
    lp = {"hc_a_phi": phi, "hc_a_alpha": alpha, "hc_a_bias": bias}
    return llama._hyper_connection(
        X, lp, "a", c, lambda u: (jnp.tanh(u @ w), None))[0]


def _out_and_grads(X, phi, alpha, bias, w, weights, c=_Sizes()):
    """``X'`` and the gradients of its sum under ``weights``."""
    def loss(*operands):
        out = _mixed(*operands, c)
        return jnp.sum(out.astype(F32) * weights), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(X, phi, alpha, bias, w)
    return out, grads


def _l2(got, ref):
    got, ref = jnp.asarray(got, F32), jnp.asarray(ref, F32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    return float(jnp.linalg.norm(got - ref)
                 / (jnp.linalg.norm(ref) + 1e-30))


def _held(got, ref, exact, what):
    """Float32: to 1e-5 of the expression. bfloat16, "at the
    expression's own rounding": no further from the expression
    evaluated in float32 on the same operands (``exact``) than one and a
    half times what the expression in bfloat16 stands off it, plus a
    rounding."""
    if exact is None:
        assert _l2(got, ref) < 1e-5, (what, _l2(got, ref))
    else:
        assert _l2(got, exact) < 1.5 * _l2(ref, exact) + 2.0 ** -9, \
            (what, _l2(got, exact), _l2(ref, exact))


CASES = [(F32, 1, 256), (F32, 2, 384), (BF16, 1, 384), (BF16, 2, 256)]
IDS = ["float32-B1-D256", "float32-B2-D384", "bfloat16-B1-D384",
       "bfloat16-B2-D256"]


def _forward(X, phi, alpha, bias, w):
    """(``u``, ``H_pre``, ``H_post``, ``H_res``, ``X'``) by whichever
    carrier ``on_kernels`` names."""
    c = _Sizes()
    if module.on_kernels(X):
        u = module._pre(X, phi, alpha, bias, llama._hc_sizes(c))[0]
    else:
        pre = llama._hc_coefficients(X, phi, alpha, bias, c)[0]
        u = (pre[..., None] * X.astype(F32)).sum(1).astype(X.dtype)
    return (u, *llama._hc_coefficients(X, phi, alpha, bias, c),
            _mixed(X, phi, alpha, bias, w))


@pytest.mark.parametrize("dtype, B, D", CASES, ids=IDS)
def test_forward_is_the_expression(monkeypatch, dtype, B, D):
    """``hvd_hc_pre_fwd`` and ``hvd_hc_post_fwd``: ``u``, the 24
    coefficients a token (through ``llama._hc_coefficients``, which on
    the kernels returns what the kernel computed) and ``X'``."""
    *operands, _ = _operands(dtype, B, D)
    ref = jax.jit(_forward)(*operands)
    exact = None if dtype == F32 else jax.jit(_forward)(
        *(x.astype(F32) for x in operands))
    monkeypatch.setattr(module, "_INTERPRET", True)
    assert module.on_kernels(operands[0])
    got = jax.jit(_forward)(*operands)
    for i, what in enumerate(("u", "H_pre", "H_post", "H_res", "X'")):
        _held(got[i], ref[i], exact and exact[i], what)
    assert got[0].dtype == got[4].dtype == dtype
    assert all(x.dtype == F32 for x in got[1:4])


@pytest.mark.parametrize("dtype, B, D", CASES, ids=IDS)
def test_every_gradient_through_both_pairs(monkeypatch, dtype, B, D):
    """``hvd_hc_post_bwd`` and ``hvd_hc_pre_bwd`` with the part between
    them: ``dX`` (its four roads summed once), ``dPhi``, ``dalpha``,
    ``dbias``, and the part's own weight, which reads ``dy`` and ``u``;
    the leaves' gradients in the leaves' dtype."""
    operands = _operands(dtype, B, D, seed=1)
    _, ref = _out_and_grads(*operands)
    exact = None if dtype == F32 else _out_and_grads(
        *(x.astype(F32) for x in operands))[1]
    monkeypatch.setattr(module, "_INTERPRET", True)
    _, got = _out_and_grads(*operands)
    for i, what in enumerate(("dX", "dPhi", "dalpha", "dbias", "dw")):
        assert got[i].dtype == operands[i].dtype, what
        _held(got[i], ref[i], exact and exact[i], what)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_dy_is_the_expressions(kernels, dtype):
    """``hvd_hc_post_bwd``'s ``dy`` alone, and the coefficients'
    cotangents it hands ``hvd_hc_pre_bwd`` (``H_post``'s and ``H_res``'s
    rows, ``H_pre``'s zero), against ``jax.vjp`` of the expression's
    last line."""
    X, phi, alpha, bias, _, weights = _operands(dtype, 1, 256, seed=2)
    y = jax.random.normal(jax.random.PRNGKey(9), (1, T, 256),
                          F32).astype(dtype)
    coef = module._pre(X, phi, alpha, bias, llama._hc_sizes(_Sizes()))[1]
    pre, post, res = module.coefficients(X, phi, alpha, bias,
                                         llama._hc_sizes(_Sizes()))

    def behind(post, res, y):
        Xf = X.astype(F32)
        mixed = sum(res[:, :, j, :, None] * Xf[:, j, None]
                    for j in range(N))
        return (mixed + post[..., None] * y.astype(F32)[:, None]
                ).astype(dtype)

    g = weights.astype(dtype)
    dpost, dres, dy = jax.vjp(behind, post, res, y)[1](g)
    got_dy, got_dc = module._post_bwd(coef, X, y, g,
                                      **module._tiling(T))
    groups = got_dc.reshape(1, N + 2, module.GROUP, T)
    tol = 1e-5 if dtype == F32 else 2.0 ** -7
    assert _l2(got_dy, dy) < tol
    assert _l2(groups[:, 1, :N], dpost) < tol
    assert _l2(groups[:, 2:, :N], dres) < tol
    assert not np.asarray(groups[:, 0]).any()
    assert not np.asarray(groups[:, :, N:]).any()


@pytest.mark.parametrize("shift", [100.0, -100.0], ids=["upper", "lower"])
def test_logits_at_an_edge_of_the_clamp(kernels, monkeypatch, shift):
    """A row of ``H_res``'s logits past an edge of the clamp is read at
    the edge and passes no gradient; the rest of the part as ever."""
    edge = jnp.zeros((N, N)).at[1].set(shift).ravel()
    operands = _operands(F32, 1, 256, seed=3, logit_shift=edge)
    out, got = _out_and_grads(*operands)
    monkeypatch.setattr(module, "_INTERPRET", False)
    ref_out, ref = _out_and_grads(*operands)
    for g, r, what in zip((out, *got), (ref_out, *ref),
                          ("X'", "dX", "dPhi", "dalpha", "dbias", "dw")):
        assert _l2(g, r) < 1e-5, what
    # the clamped row's bias moves nothing; its neighbours' do
    dbias = np.asarray(got[3])[2 * N:].reshape(N, N)
    assert not dbias[1].any() and np.abs(dbias[0]).min() > 0


@pytest.mark.parametrize("what", ["nineteen-iterations", "post-scale-1"])
def test_the_static_arguments_are_read_at_trace_time(kernels, monkeypatch,
                                                     what):
    """Nineteen iterations, or ``llama._HC_POST_SCALE = 1.0``, build
    another program: the result CHANGES (the adapter's planted faults
    rest on it), and to what the expression makes of the same fault."""
    *operands, _ = _operands(F32, 1, 256, seed=4)
    plain = jax.jit(_mixed)(*operands)
    c = _Sizes()
    if what == "nineteen-iterations":
        c = dataclasses.replace(c, hc_sinkhorn_iters=19)
    else:
        monkeypatch.setattr(llama, "_HC_POST_SCALE", 1.0)
    planted = jax.jit(lambda *a: _mixed(*a, c))(*operands)
    assert _l2(planted, plain) > 1e-3
    monkeypatch.setattr(module, "_INTERPRET", False)
    assert _l2(planted, jax.jit(lambda *a: _mixed(*a, c))(*operands)) < 1e-5


@pytest.mark.parametrize("what, shape, interpret, sharded, says", [
    ("whole slabs, whole tiles", (1, 4, 256, 256), True, False, True),
    ("a sequence of one tile", (2, 4, 128, 384), True, False, True),
    ("a D of no whole slabs", (1, 4, 256, 200), True, False, False),
    ("a T no tile divides", (1, 4, 192, 256), True, False, False),
    ("a mesh axis divides the carry", (1, 4, 256, 256), True, True, False),
    ("more streams than a group holds", (1, 9, 256, 256), True, False,
     False),
    ("operands off the chip", (1, 4, 256, 256), False, False, False),
])
def test_on_kernels(monkeypatch, what, shape, interpret, sharded, says):
    monkeypatch.setattr(module, "_INTERPRET", interpret)
    X = jnp.zeros(shape, BF16)
    assert module.on_kernels(X, sharded) is says, what


def test_off_the_kernels_the_expression_runs(monkeypatch):
    """Where ``on_kernels`` says no the model's path holds no Mosaic
    call, and where it says yes the four of them, each by its name."""
    *operands, weights = _operands(F32, 1, 256)
    names = ("hvd_hc_pre_fwd", "hvd_hc_pre_bwd", "hvd_hc_post_fwd",
             "hvd_hc_post_bwd")

    def text():
        return str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(_mixed(*a) * weights)))(*operands))

    assert "pallas_call" not in text()
    monkeypatch.setattr(module, "_INTERPRET", True)
    assert all(name in text() for name in names)


def test_xing4_against_the_reference_on_the_kernels(monkeypatch):
    """``test_xing4_reference.py``'s comparison once more, loss and
    every gradient leaf of two layers and the MTP module's against the
    plain float32 reference, with every part's stream mixing on the
    kernels (a lane slab 8 wide: ``d_model`` is 32), under remat
    "attn"."""
    monkeypatch.setattr(module, "_INTERPRET", True)
    monkeypatch.setattr(module, "LANES", 8)
    cfg = dataclasses.replace(xing4._cfg(), remat="attn")
    params, batch = xing4._params(cfg), xing4._batch(cfg)
    want, want_grads = xing4._reference(cfg)(params, batch)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: llama_loss(p, batch, cfg)))(params))
    assert "hvd_hc_pre_bwd" in text and "hvd_hc_post_bwd" in text
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg)))(params)
    assert abs(float(loss) - float(want)) <= xing4.TOL * float(want)
    worst, leaf = xing4._worst_leaf(grads, want_grads)
    assert worst <= xing4.GRAD_TOL, (leaf, worst)
