"""Oracle for the KDA decode step over the stacked state."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def kda_decode_ref(q, k, v, g, beta, state, layer):
    """One token for every slot, in the published shapes: q, k, g (B, H,
    d_k), v (B, H, d_v), beta (B, H) float32; the stacked state (L, B, H,
    d_v, d_k) in the kernel's layout (each head's S transposed). Updates
    layer ``layer`` of the stack. Returns (o (B, H, d_v), the stack)."""
    m = lax.dynamic_index_in_dim(state, layer, keepdims=False)
    m = m * jnp.exp(g)[:, :, None, :]
    kv = jnp.einsum("bhvk,bhk->bhv", m, k, precision=lax.Precision.HIGHEST)
    u = beta[..., None] * (v - kv)
    m = m + u[..., None] * k[:, :, None, :]
    o = jnp.einsum("bhvk,bhk->bhv", m, q, precision=lax.Precision.HIGHEST)
    return o, lax.dynamic_update_index_in_dim(state, m.astype(state.dtype),
                                              layer, 0)
