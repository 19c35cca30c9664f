"""Pure-jnp oracle of the grouped matmul: ``lax.ragged_dot`` over the
same contiguous groups (rows past the sizes' sum come out zero)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_ref(lhs: jnp.ndarray, rhs: jnp.ndarray,
            group_sizes: jnp.ndarray) -> jnp.ndarray:
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)
