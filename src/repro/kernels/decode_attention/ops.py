"""Jit'd wrapper for the flash-decode kernel (forward only — decode has no
backward pass)."""

from __future__ import annotations

import jax

from repro.kernels import interpret_default
from repro.kernels.decode_attention import kernel as K


def decode_attention(q, k, v, kv_len, *, scale: float | None = None,
                     block_kv: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """q: (B, H, hd); k/v: (B, Smax, Hkv, hd); kv_len: (B,) or scalar."""
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = interpret_default()
    return K.decode_attention_kernel(q, k, v, kv_len, scale=float(scale),
                                     block_kv=int(block_kv),
                                     interpret=bool(interpret))


def latent_decode_attention(q, cache, kv_len, *, scale: float,
                            value_dim: int, block_kv: int = 512,
                            interpret: bool | None = None) -> jax.Array:
    """q: (B, H, C); cache: (B, Smax, C) latent rows; kv_len: (B,).
    Scores over all C columns, values from the first ``value_dim``."""
    if interpret is None:
        interpret = interpret_default()
    return K.latent_decode_attention_kernel(
        q, cache, kv_len, scale=float(scale), value_dim=int(value_dim),
        block_kv=int(block_kv), interpret=bool(interpret))
