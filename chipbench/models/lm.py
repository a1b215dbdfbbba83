"""Model adapter for kind "lm": a dense llama-family decoder run through
the program's own ``LlamaConfig`` / ``llama_init`` / ``llama_loss``,
sized by a configuration file that holds the published ``config.json``
keys. Nothing of the model is re-implemented here except the plain
float32 reference that ``correct`` is decided against.
"""

import jax
import jax.numpy as jnp

from chipbench import peaks
from chipbench.compare import rel_err

# published config.json key -> LlamaConfig field
_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps"}

# Normalized max-abs error bounds, bf16 operands (8 mantissa bits; the
# kernel feeds bf16 probabilities to the MXU where the reference keeps
# f32): forward, backward. The smoke's bounds (chip_smoke.KERNEL_TOL).
KERNEL_TOL = {"fwd": 2e-2, "bwd": 5e-2}
# bf16 weights and activations against the float32 reference on the same
# weights: logits of |x| ~ 1 carry ~3 bf16 roundings a layer. Computing
# a matmul in a lower precision than bf16 (fp8: 3 mantissa bits) moves
# logits by > 0.1 and fails this.
LOGITS_TOL = 5e-2
REFERENCE_TOKENS = 512


def reference_logits(params, tokens, cfg):
    """The plain reference: the architecture's forward pass in
    straightforward float32 ``jax.numpy`` — RMSNorm, rotary embedding
    (half-split), grouped-query causal attention with an explicit mask,
    SiLU-gated MLP — no kernel, no scan, no remat. Reads the program's
    parameter tree; shares no code with ``models/llama.py``."""
    f32 = jnp.float32
    hd = cfg.d_model // cfg.n_heads
    rep = cfg.n_heads // cfg.n_kv_heads
    b, t = tokens.shape

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + cfg.norm_eps) * g.astype(f32)

    inv = cfg.rope_theta ** (-jnp.arange(0, hd // 2, dtype=f32)
                             / (hd // 2))
    ang = jnp.arange(t, dtype=f32)[:, None] * inv          # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], -1)

    mask = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"].astype(f32)[tokens]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda w: w[i].astype(f32), params["layers"])
            h = norm(x, lp["attn_norm"])
            q = rope((h @ lp["wq"]).reshape(b, t, cfg.n_heads, hd))
            k = rope((h @ lp["wk"]).reshape(b, t, cfg.n_kv_heads, hd))
            v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
            x = x + a @ lp["wo"]
            h = norm(x, lp["mlp_norm"])
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
                @ lp["w_down"]
        x = norm(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)


class Model:
    unit = "tokens"

    def __init__(self, config, traffic):
        from horovod_tpu.models import LlamaConfig

        a = config["assumed"]
        self.cfg = LlamaConfig(
            **{field: config[key] for key, field in _KEYS.items()},
            dtype="bfloat16", remat=a["remat"],
            param_dtype=a["param_dtype"])
        self.batch_size, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch_size * self.seq
        self.opt = a["optimizer"]
        self.compiler_options = dict(a.get("compiler_options") or {})
        self.has_state = False

    def init(self, key):
        from horovod_tpu.models import llama_init

        return llama_init(self.cfg, key), ()

    def loss(self, params, state, batch):
        from horovod_tpu.models import llama_loss

        return llama_loss(params, batch, self.cfg), state

    def batch(self, key):
        tokens = jax.random.randint(key, (self.batch_size, self.seq), 0,
                                    self.cfg.vocab_size)
        return {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def optimizer(self, ranks):
        import optax

        assert self.opt["name"] == "adam", self.opt
        return optax.adam(self.opt["learning_rate"])

    def flops_per_unit(self):
        c = self.cfg
        return peaks.lm_train_flops_per_token(
            c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
            c.n_layers, c.vocab_size, self.seq)

    def check_lowering(self, text, on_tpu):
        """The grad program must hold the flash kernel, not the
        reference branch."""
        if on_tpu and "tpu_custom_call" not in text:
            return "grad program lowered without a tpu_custom_call: " \
                   "the flash kernel's reference branch ran"
        return None

    def check_outputs(self, params, key, say):
        """Outside the window, once a run: (1) the flash kernel at the
        cell's attention shape against ``blockwise_attention``, forward
        and gradients; (2) the program's logits on a seeded sample
        against the plain float32 reference on the same weights.
        Returns a list of faults (empty = correct)."""
        from horovod_tpu.models import llama_forward
        from horovod_tpu.ops import flash_attention
        from horovod_tpu.parallel.ring_attention import blockwise_attention

        c, faults = self.cfg, []
        ks = jax.random.split(key, 5)
        shape = (self.batch_size, self.seq, c.n_heads, c.head_dim)
        kv = (self.batch_size, self.seq, c.n_kv_heads, c.head_dim)
        q = jax.random.normal(ks[0], shape, jnp.bfloat16)
        k = jax.random.normal(ks[1], kv, jnp.bfloat16)
        v = jax.random.normal(ks[2], kv, jnp.bfloat16)
        w = jax.random.normal(ks[3], shape, jnp.bfloat16)

        def grads_of(attn):   # w rides as an argument, never closed over
            def f(q, k, v, w):
                out = attn(q, k, v, causal=True)
                return jnp.sum(out.astype(jnp.float32)
                               * w.astype(jnp.float32)), out
            return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

        got, out = grads_of(flash_attention)(q, k, v, w)
        ref, out_ref = grads_of(blockwise_attention)(q, k, v, w)
        err = {"fwd": rel_err(out, out_ref)}
        for name, g, r in zip(("dq", "dk", "dv"), got, ref):
            err[name] = rel_err(g, r)
        say(event="flash_vs_blockwise", shape=list(shape), err=err,
            tol=KERNEL_TOL)
        for name, e in err.items():
            if not e <= KERNEL_TOL["fwd" if name == "fwd" else "bwd"]:
                faults.append(f"flash {name} error {e} vs blockwise")
        del q, k, v, w, got, ref, out, out_ref

        tokens = jax.random.randint(ks[4], (1, REFERENCE_TOKENS), 0,
                                    c.vocab_size)
        got = jax.jit(lambda p, t: llama_forward(p, t, c))(params, tokens)
        ref = jax.jit(lambda p, t: reference_logits(p, t, c))(params,
                                                              tokens)
        e = rel_err(got, ref)
        say(event="logits_vs_reference", tokens=REFERENCE_TOKENS, err=e,
            tol=LOGITS_TOL)
        if not e <= LOGITS_TOL:
            faults.append(f"logits error {e} vs the float32 reference")
        return faults
