"""Plain float32 reference of a dense GQA decoder (Qwen2 and Granite 3.0
families): pre-norm RMSNorm blocks, rotary positions (halves rotated),
grouped-query attention with causal softmax, SwiGLU MLP, tied or untied
embeddings and Granite's scalar multipliers. It follows the published
architectures, reads its sizes from the configuration file, draws its
weights from the seed (``bench/benchlib/weights.py``) one layer at a time,
and imports nothing of the program under test. Every matrix product runs
at ``HIGHEST`` precision.

``precision="fp8"`` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale, accumulation and all
other arithmetic stay in float32.

This module is the one place that knows the architecture
(``benchlib/spec.py`` lists what such a module provides): its weight
leaves (``leaf_specs``), the program configuration that computes it
(``check_program``) and its useful work (``generate_flops``,
``decode_attention_work``, from ``benchlib/flops.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import flops, weights
from benchlib.weights import Leaf

HI = jax.lax.Precision.HIGHEST
#: ModelConfig field -> configuration-file key, for the program check
_FIELDS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads", "hd": "head_dim",
           "d_ff": "intermediate_size", "vocab_size": "vocab_size",
           "qkv_bias": "qkv_bias", "tie_embeddings": "tie_word_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}
_SCALARS = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "attention_multiplier": 0.0, "logits_scaling": 1.0}
#: (family, mlp_act, qk_norm, attn_softcap, use_rope) of this architecture
_ARCH = ("dense", "silu", False, 0.0, True)


def leaf_specs(m: dict) -> dict[str, Leaf]:
    """Every weight leaf of the program's tree; ``layers.*`` are stacked
    over the depth, the rest are drawn once. Matrices take the standard
    deviation of their true fan-in, the product of the dimensions they sum
    over; norm scales sit near 1 and biases near 0, both non-trivial so
    that a dropped scale or bias shows."""
    d, h, kv, hd, f = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    n = m["num_hidden_layers"]
    # q and k are scaled so that attention logits have unit variance under
    # the configured attention multiplier (Granite's 1/64 would otherwise
    # leave attention uniform and the continuation a function of the last
    # token alone)
    scale = m.get("attention_multiplier") or hd ** -0.5
    qk = (math.sqrt(hd) * scale) ** -0.5 / math.sqrt(d)
    specs = {
        # the embedded input (times the embedding multiplier) has unit norm
        # per row; a larger one makes tied logits favour the input token
        # so strongly that greedy decoding repeats it forever
        "embedding": Leaf((m["vocab_size"], d),
                          1 / (m.get("embedding_multiplier", 1.0)
                               * math.sqrt(d)), 0.0, vocab_axis=0),
        "ln_final": Leaf((d,), 0.05, 1.0),
        "layers.ln_attn": Leaf((d,), 0.05, 1.0, n),
        "layers.ln_mlp": Leaf((d,), 0.05, 1.0, n),
        "layers.attn.wq": Leaf((d, h, hd), qk, 0.0, n),
        "layers.attn.wk": Leaf((d, kv, hd), qk, 0.0, n),
        "layers.attn.wv": Leaf((d, kv, hd), 1 / math.sqrt(d), 0.0, n),
        "layers.attn.wo": Leaf((h, hd, d), 1 / math.sqrt(h * hd), 0.0, n),
        "layers.mlp.w_gate": Leaf((d, f), 1 / math.sqrt(d), 0.0, n),
        "layers.mlp.w_up": Leaf((d, f), 1 / math.sqrt(d), 0.0, n),
        "layers.mlp.w_down": Leaf((f, d), 1 / math.sqrt(f), 0.0, n),
    }
    if m.get("qkv_bias"):
        specs["layers.attn.bq"] = Leaf((h, hd), 0.1, 0.0, n)
        specs["layers.attn.bk"] = Leaf((kv, hd), 0.1, 0.0, n)
        specs["layers.attn.bv"] = Leaf((kv, hd), 0.1, 0.0, n)
    if not m["tie_word_embeddings"]:
        specs["lm_head"] = Leaf((d, m["vocab_size"]), 1 / math.sqrt(d), 0.0,
                                vocab_axis=1)
    return specs


def check_program(cfg, m: dict) -> dict:
    """The program's ``ModelConfig`` against the file: each field that
    differs, as (program's, file's)."""
    wrong = {f: (getattr(cfg, f), m[k]) for f, k in _FIELDS.items()
             if getattr(cfg, f) != m[k]}
    wrong.update({f: (getattr(cfg, f), m.get(f, d))
                  for f, d in _SCALARS.items()
                  if getattr(cfg, f) != m.get(f, d)})
    arch = (cfg.family, cfg.mlp_act, cfg.qk_norm, cfg.attn_softcap,
            cfg.use_rope)
    if arch != _ARCH:
        wrong["architecture"] = (arch, _ARCH)
    return wrong


#: model operations of one request: prefill, then ``new_tokens - 1``
#: decode steps
generate_flops = flops.generate_flops


def decode_attention_work(m: dict, context: int) -> tuple[int, int]:
    """(operations, bytes) of the decode-attention kernel for one sequence
    over ``context`` positions, in every layer (each holds attention)."""
    ops, nbytes = flops.decode_attention_work(m, context)
    return ops * m["num_hidden_layers"], nbytes * m["num_hidden_layers"]


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
def _head(x, rows, ln, head, static):
    eps, logits_scaling, low, spec = static
    h = _rms(x[rows[:, 0], rows[:, 1]], ln, eps)
    return _mm(spec, h, head, low) / logits_scaling


def logits_at(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of the
    causal forward pass over ``tokens`` (B, S); (n, vocab) float32."""
    low = precision == "fp8"
    eps = float(m["rms_norm_eps"])
    scale = float(m.get("attention_multiplier") or m["head_dim"] ** -0.5)
    resid = float(m.get("residual_multiplier", 1.0))
    static = (eps, float(m["rope_theta"]), scale, resid, low)
    specs = leaf_specs(m)
    emb = weights.draw(specs, seed, "embedding")
    x = jnp.take(emb, jnp.asarray(tokens, jnp.int32), axis=0)
    x = x * float(m.get("embedding_multiplier", 1.0))
    # a tied head is the embedding (vocab, d); an untied one is (d, vocab)
    tied = m["tie_word_embeddings"]
    head = emb if tied else weights.draw(specs, seed, "lm_head")
    del emb
    stacked = [n for n, leaf in specs.items() if leaf.depth]
    for layer in range(m["num_hidden_layers"]):
        w = {n.removeprefix("layers."): weights.draw(specs, seed, n, layer)
             for n in stacked}
        x = _layer(x, w, static)
    ln = weights.draw(specs, seed, "ln_final")
    out = _head(x, jnp.asarray(rows, jnp.int32), ln, head,
                (eps, float(m.get("logits_scaling", 1.0)), low,
                 "nd,vd->nv" if tied else "nd,dv->nv"))
    return np.asarray(out)
