"""Plain float32 reference of a dense GQA decoder (Qwen2 and Granite 3.0
families): pre-norm RMSNorm blocks, rotary positions (halves rotated),
grouped-query attention with causal softmax, SwiGLU MLP, tied embeddings
and Granite's scalar multipliers. It follows the published architectures,
reads its sizes from the configuration file, draws its weights from the
seed (``bench/lib/weights.py``) one layer at a time, and imports nothing
of the program under test. Every matrix product runs at ``HIGHEST``
precision.

``precision="fp8"`` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale, accumulation and all
other arithmetic stay in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights

HI = jax.lax.Precision.HIGHEST
_LAYER_LEAVES = ("ln_attn", "ln_mlp", "attn.wq", "attn.wk", "attn.wv",
                 "attn.wo", "mlp.w_gate", "mlp.w_up", "mlp.w_down")
_BIAS_LEAVES = ("attn.bq", "attn.bk", "attn.bv")


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, low: bool):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(x, w, static):
    eps, theta, scale, resid, low = static
    b, s, _ = x.shape
    h = _rms(x, w["ln_attn"], eps)
    q = _mm("bsd,dhk->bshk", h, w["attn.wq"], low)
    k = _mm("bsd,dhk->bshk", h, w["attn.wk"], low)
    v = _mm("bsd,dhk->bshk", h, w["attn.wv"], low)
    if "attn.bq" in w:
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    heads, kv = q.shape[2], k.shape[2]
    q = q.reshape(b, s, kv, heads // kv, -1)
    logits = _mm("bskgh,btkh->bkgst", q, k, low) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = _mm("bkgst,btkh->bskgh", p, v, low).reshape(b, s, heads, -1)
    x = x + resid * _mm("bshk,hkd->bsd", o, w["attn.wo"], low)
    h = _rms(x, w["ln_mlp"], eps)
    g = _mm("bsd,df->bsf", h, w["mlp.w_gate"], low)
    u = _mm("bsd,df->bsf", h, w["mlp.w_up"], low)
    return x + resid * _mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                           w["mlp.w_down"], low)


@functools.partial(jax.jit, static_argnums=(4,))
def _head(x, rows, ln, emb, static):
    eps, logits_scaling, low = static
    h = _rms(x[rows[:, 0], rows[:, 1]], ln, eps)
    return _mm("nd,vd->nv", h, emb, low) / logits_scaling


def logits_at(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of the
    causal forward pass over ``tokens`` (B, S); (n, vocab) float32."""
    low = precision == "fp8"
    eps = float(m["rms_norm_eps"])
    scale = float(m.get("attention_multiplier") or m["head_dim"] ** -0.5)
    resid = float(m.get("residual_multiplier", 1.0))
    static = (eps, float(m["rope_theta"]), scale, resid, low)
    emb = weights.draw(m, seed, "embedding")
    x = jnp.take(emb, jnp.asarray(tokens, jnp.int32), axis=0)
    x = x * float(m.get("embedding_multiplier", 1.0))
    leaves = _LAYER_LEAVES + (_BIAS_LEAVES if m.get("qkv_bias") else ())
    for layer in range(m["num_hidden_layers"]):
        w = {n: weights.draw(m, seed, "layers." + n, layer) for n in leaves}
        x = _layer(x, w, static)
    ln = weights.draw(m, seed, "ln_final")
    out = _head(x, jnp.asarray(rows, jnp.int32), ln, emb,
                (eps, float(m.get("logits_scaling", 1.0)), low))
    return np.asarray(out)
