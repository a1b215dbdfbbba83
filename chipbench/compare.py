"""The comparison that decides ``correct`` against a plain reference."""

import jax.numpy as jnp


def rel_err(got, ref):
    """Normalized max-abs error: max |got - ref| / max |ref|, in f32."""
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
