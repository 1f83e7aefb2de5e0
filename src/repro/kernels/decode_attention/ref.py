"""Oracle for single-token flash decode over a long KV cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e30


def _layer(cache: jax.Array, layer) -> jax.Array:
    return cache if layer is None else cache[layer]


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array | int, *, layer=None,
                         scale: float | None = None) -> jax.Array:
    """q: (B, H, hd) one token; k/v: (B, Hkv, hd, Smax), or the stacked
    (L, B, Hkv, hd, Smax) with ``layer`` the index to read; kv_len: (B,)
    or int.

    Attends to cache positions [0, kv_len) per batch row."""
    k, v = _layer(k, layer), _layer(v, layer)
    b, h, hd = q.shape
    hkv, smax = k.shape[1], k.shape[3]
    g = h // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum("bkgh,bkht->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    lens = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
    ok = jnp.arange(smax)[None, :] < lens[:, None]            # (B, Smax)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bkht->bkgh", p.astype(v.dtype), v)
    return out.reshape(b, h, hd).astype(q.dtype)


def latent_decode_attention_ref(q: jax.Array, cache: jax.Array,
                                kv_len: jax.Array | int, *, scale: float,
                                value_dim: int, layer=None) -> jax.Array:
    """q: (B, H, C); cache: (B, C, Smax) latent rows shared by all heads,
    or the stacked (L, B, C, Smax) with ``layer`` the index to read;
    scores over all C, values from the first ``value_dim``."""
    cache = _layer(cache, layer)
    smax = cache.shape[2]
    logits = jnp.einsum("bhc,bct->bht", q.astype(jnp.float32),
                        cache.astype(jnp.float32)) * scale
    lens = jnp.broadcast_to(jnp.asarray(kv_len), (q.shape[0],))
    ok = jnp.arange(smax)[None, :] < lens[:, None]
    logits = jnp.where(ok[:, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,brt->bhr", p,
                     cache[:, :value_dim].astype(jnp.float32))
    return out.astype(q.dtype)
