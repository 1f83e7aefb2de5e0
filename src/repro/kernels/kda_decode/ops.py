"""Jit'd wrapper for the KDA decode kernel (forward only).

Takes the published shapes and lays them out for the kernel: q, k and the
decay as rows, v as columns, beta one per head. The state is the stacked
``(L, B, H, d_v, d_k)`` buffer (each head's S transposed) and ``layer``
the index to update in place."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.kda_decode import kernel as K


def kda_decode(q, k, v, g, beta, state, layer, *, heads_block: int = 8,
               interpret: bool | None = None):
    """q, k, g (B, H, d_k): q and k normalised and q scaled, g the
    log-decay; v (B, H, d_v); beta (B, H); state (L, B, H, d_v, d_k)
    float32. Returns (o (B, H, d_v) float32, the state with layer
    ``layer`` advanced one token)."""
    if interpret is None:
        interpret = interpret_default()
    f32 = jnp.float32

    def row(t):
        return t.astype(f32)[:, :, None, :]

    o, state = K.kda_decode_kernel(
        row(q), row(k), row(g), v.astype(f32)[..., None],
        beta.astype(f32)[..., None, None], state, layer,
        heads_block=int(heads_block), interpret=bool(interpret))
    return o[..., 0], state
