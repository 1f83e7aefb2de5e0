"""Oracle for the Mamba-2 decode step over the stacked state."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ssm_decode_ref(x, dt, a, d, b, c, state, layer):
    """One token for every slot, in the published shapes: x (B, H, P), dt
    (B, H) after softplus, a = -exp(A_log) and d = D (H,), b and c (B, G,
    N) float32; the stacked state (L, B, N, H*P) in the kernel's layout.
    Updates layer ``layer`` of the stack. Returns (y (B, H, P), the
    stack)."""
    nb, h, p = x.shape
    g, n = b.shape[1:]
    s = lax.dynamic_index_in_dim(state, layer, keepdims=False)
    s = s.reshape(nb, n, h, p).transpose(0, 2, 3, 1)      # (B, H, P, N)
    bh = jnp.repeat(b, h // g, axis=1)                     # (B, H, N)
    ch = jnp.repeat(c, h // g, axis=1)
    decay = jnp.exp(dt * a)[..., None, None]
    s = s * decay + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, ch,
                   precision=lax.Precision.HIGHEST) + d[:, None] * x
    s = s.transpose(0, 3, 1, 2).reshape(nb, n, h * p)
    return y, lax.dynamic_update_index_in_dim(state, s.astype(state.dtype),
                                              layer, 0)
