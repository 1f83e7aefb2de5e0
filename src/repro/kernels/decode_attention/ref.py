"""Oracle for single-token flash decode over a long KV cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e30


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array | int, *,
                         scale: float | None = None) -> jax.Array:
    """q: (B, H, hd) one token; k/v: (B, Smax, Hkv, hd); kv_len: (B,) or int.

    Attends to cache positions [0, kv_len) per batch row."""
    b, h, hd = q.shape
    smax, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    lens = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
    ok = jnp.arange(smax)[None, :] < lens[:, None]            # (B, Smax)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p.astype(v.dtype), v)
    return out.reshape(b, h, hd).astype(q.dtype)


def latent_decode_attention_ref(q: jax.Array, cache: jax.Array,
                                kv_len: jax.Array | int, *, scale: float,
                                value_dim: int) -> jax.Array:
    """q: (B, H, C); cache: (B, Smax, C) latent rows shared by all heads;
    scores over all C columns, values from the first ``value_dim``."""
    smax = cache.shape[1]
    logits = jnp.einsum("bhc,btc->bht", q.astype(jnp.float32),
                        cache.astype(jnp.float32)) * scale
    lens = jnp.broadcast_to(jnp.asarray(kv_len), (q.shape[0],))
    ok = jnp.arange(smax)[None, :] < lens[:, None]
    logits = jnp.where(ok[:, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,btr->bhr", p,
                     cache[..., :value_dim].astype(jnp.float32))
    return out.astype(q.dtype)
