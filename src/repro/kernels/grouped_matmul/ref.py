"""Pure-jnp oracle for the grouped expert matmul: ``lax.ragged_dot``.

Rows ``[off_g, off_g + sizes[g])`` of ``lhs`` (the entries routed to
group ``g``, sorted by group) are multiplied by ``rhs[g]``; rows past
``sum(sizes)`` come back as zeros."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_gmm_ref(lhs, rhs, sizes):
    """lhs: (M, K); rhs: (G, K, N); sizes: (G,) int32 -> (M, N)."""
    out = jax.lax.ragged_dot(lhs, rhs, sizes,
                             preferred_element_type=jnp.float32)
    keep = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
    return jnp.where(keep[:, None], out, 0).astype(lhs.dtype)
