"""Jit'd wrapper for the Mamba-2 decode kernel (forward only).

Takes the published shapes and lays them out for the kernel: dt, A and D
repeated over each head's P channels as rows, B and C as columns. The
state is the stacked ``(L, B, N, H*P)`` buffer and ``layer`` the index to
update in place."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.ssm_decode import kernel as K


def ssm_decode(x, dt, a, d, b, c, state, layer, *, block: int = 2048,
               interpret: bool | None = None):
    """x (B, H, P); dt (B, H) after softplus; a = -exp(A_log), d = D (H,);
    b, c (B, G, N); state (L, B, N, H*P) float32. Returns (y (B, H, P)
    float32, the state with layer ``layer`` advanced one token)."""
    if interpret is None:
        interpret = interpret_default()
    nb, h, p = x.shape
    f32 = jnp.float32
    y, state = K.ssm_decode_kernel(
        x.astype(f32).reshape(nb, 1, h * p),
        jnp.repeat(dt.astype(f32), p, axis=1)[:, None],
        jnp.repeat(a.astype(f32), p)[None], jnp.repeat(d.astype(f32), p)[None],
        b.astype(f32)[..., None], c.astype(f32)[..., None], state, layer,
        block=int(block), interpret=bool(interpret))
    return y.reshape(nb, h, p), state
