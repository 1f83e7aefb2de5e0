"""Jit'd wrapper for the flash-decode kernel (forward only — decode has no
backward pass).

Both entry points take either one layer's cache or, with ``layer``, the
model's stacked cache and the index of the layer to read; the kernel
reads that layer in place."""

from __future__ import annotations

import jax

from repro.kernels import interpret_default
from repro.kernels.decode_attention import kernel as K


def _stack(cache, layer):
    """(the stacked cache, the layer to read): one layer's cache becomes a
    stack of one, a free reshape."""
    return (cache[None], 0) if layer is None else (cache, layer)


def decode_attention(q, k, v, kv_len, *, layer=None,
                     scale: float | None = None, block_kv: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """q: (B, H, hd); k/v: (B, Hkv, hd, Smax), or (L, B, Hkv, hd, Smax)
    with ``layer`` the index to read; kv_len: (B,) or scalar."""
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = interpret_default()
    k, at = _stack(k, layer)
    v, _ = _stack(v, layer)
    return K.decode_attention_kernel(q, k, v, kv_len, at, scale=float(scale),
                                     block_kv=int(block_kv),
                                     interpret=bool(interpret))


def latent_decode_attention(q, cache, kv_len, *, scale: float,
                            value_dim: int, layer=None, block_kv: int = 512,
                            interpret: bool | None = None) -> jax.Array:
    """q: (B, H, C); cache: (B, C, Smax) latent rows, or (L, B, C, Smax)
    with ``layer`` the index to read; kv_len: (B,). Scores over all C,
    values from the first ``value_dim``."""
    if interpret is None:
        interpret = interpret_default()
    cache, at = _stack(cache, layer)
    return K.latent_decode_attention_kernel(
        q, cache, kv_len, at, scale=float(scale), value_dim=int(value_dim),
        block_kv=int(block_kv), interpret=bool(interpret))
