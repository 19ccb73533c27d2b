"""Plain references: the same mathematics in straightforward ``jax.numpy``,
float32 at ``highest`` matmul precision, sharing no code with ``heat_tpu``."""

import jax.numpy as jnp


def rel_err(got, want) -> float:
    """Largest deviation as a share of the largest wanted value."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
