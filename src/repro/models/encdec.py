"""Whisper-large-v3 transformer backbone (encoder-decoder).

Per the assignment the conv/mel frontend is a STUB: ``input_specs()``
supplies precomputed frame embeddings (B, num_frames, d_model) as the
encoder input. The backbone is faithful otherwise: LayerNorm (with bias),
plain GELU MLPs (not gated), MHA with kv == heads, tied decoder embedding.
Position embeddings are sinusoidal for both stacks (whisper uses learned
decoder positions — swapped for table-free sinusoidal so one config serves
arbitrary sequence lengths).

Both stacks are homogeneous and scanned.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention as attn
from repro.models.common import (
    ModelConfig,
    ParamSpec,
    layer_norm,
    maybe_remat,
    shard,
    sinusoidal_positions,
    softmax_cross_entropy,
    stack_specs,
)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _ln_specs(d: int) -> dict[str, ParamSpec]:
    return {"w": ParamSpec((d,), ("embed",), init="ones"),
            "b": ParamSpec((d,), ("embed",), init="zeros")}


def _plain_mlp_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": ParamSpec((d, f), ("embed", "ffn")),
        "b1": ParamSpec((f,), ("ffn",), init="zeros"),
        "w2": ParamSpec((f, d), ("ffn", "embed")),
        "b2": ParamSpec((d,), ("embed",), init="zeros"),
    }


def _enc_layer_specs(cfg: ModelConfig) -> dict[str, Any]:
    return {
        "ln1": _ln_specs(cfg.d_model),
        "attn": attn.make_attn_specs(cfg),
        "ln2": _ln_specs(cfg.d_model),
        "mlp": _plain_mlp_specs(cfg),
    }


def _dec_layer_specs(cfg: ModelConfig) -> dict[str, Any]:
    return {
        "ln1": _ln_specs(cfg.d_model),
        "self_attn": attn.make_attn_specs(cfg),
        "ln2": _ln_specs(cfg.d_model),
        "cross_attn": attn.make_attn_specs(cfg, cross=True),
        "ln3": _ln_specs(cfg.d_model),
        "mlp": _plain_mlp_specs(cfg),
    }


def make_whisper_specs(cfg: ModelConfig) -> dict[str, Any]:
    enc_layers = cfg.encoder_layers or cfg.num_layers
    return {
        "embedding": ParamSpec((cfg.padded_vocab, cfg.d_model),
                               ("vocab", "embed")),
        "enc_layers": stack_specs(_enc_layer_specs(cfg), enc_layers),
        "enc_ln": _ln_specs(cfg.d_model),
        "dec_layers": stack_specs(_dec_layer_specs(cfg), cfg.num_layers),
        "dec_ln": _ln_specs(cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _mlp(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w1"].astype(dt)) + p["b1"].astype(dt)
    h = jax.nn.gelu(h)
    h = shard(h, "batch", None, "ffn_sharded")
    return jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(dt)) + p["b2"].astype(dt)


def _ln(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return layer_norm(x, p["w"], p["b"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: dict[str, Any], frames: jax.Array
           ) -> jax.Array:
    """frames: (B, T, D) precomputed frame embeddings (stub frontend)."""
    dt = cfg.activation_dtype
    x = frames.astype(dt)
    pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
    x = x + pos[None]
    x = shard(x, "batch", "act_seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(h, p):
        a = attn.attn_forward(cfg, p["attn"], _ln(cfg, p["ln1"], h),
                              positions, causal=False)
        h = h + a
        h = h + _mlp(cfg, p["mlp"], _ln(cfg, p["ln2"], h))
        h = shard(h, "batch", "act_seq", None)
        return h, None

    body = maybe_remat(body, cfg.remat_policy)
    x, _ = lax.scan(body, x, params["enc_layers"])
    return _ln(cfg, params["enc_ln"], x)


# ---------------------------------------------------------------------------
# Decoder (training / teacher-forced)
# ---------------------------------------------------------------------------

def decode_train(cfg: ModelConfig, params: dict[str, Any], tokens: jax.Array,
                 enc_out: jax.Array) -> jax.Array:
    dt = cfg.activation_dtype
    x = jnp.take(params["embedding"].astype(dt), tokens, axis=0)
    pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
    x = x + pos[None]
    x = shard(x, "batch", "act_seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    enc_positions = jnp.arange(enc_out.shape[1], dtype=jnp.int32)

    def body(h, p):
        a = attn.attn_forward(cfg, p["self_attn"], _ln(cfg, p["ln1"], h),
                              positions, causal=True)
        h = h + a
        c = attn.attn_forward(cfg, p["cross_attn"], _ln(cfg, p["ln2"], h),
                              positions, causal=False, kv_x=enc_out,
                              kv_positions=enc_positions)
        h = h + c
        h = h + _mlp(cfg, p["mlp"], _ln(cfg, p["ln3"], h))
        h = shard(h, "batch", "act_seq", None)
        return h, None

    body = maybe_remat(body, cfg.remat_policy)
    x, _ = lax.scan(body, x, params["dec_layers"])
    x = _ln(cfg, params["dec_ln"], x)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(dt))
    return shard(logits, "batch", "act_seq", "vocab_sharded")


def whisper_loss(cfg: ModelConfig, params: dict[str, Any],
                 batch: dict[str, jax.Array]):
    enc_out = encode(cfg, params, batch["frames"])
    logits = decode_train(cfg, params, batch["tokens"], enc_out)
    loss, denom = softmax_cross_entropy(
        logits, batch["labels"], batch.get("mask"), cfg.vocab_size)
    return loss, {"ce_loss": loss, "tokens": denom,
                  "aux_loss": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_whisper_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Self-attn KV cache + cross-attn KV (filled at prefill)."""
    enc_layers = cfg.encoder_layers or cfg.num_layers
    del enc_layers
    hkv, hd = cfg.kv_heads_eff, cfg.hd
    t = cfg.num_frames
    return {
        "self": attn.init_kv_cache(cfg, batch, max_len, layers=cfg.num_layers),
        "cross_k": jnp.zeros((cfg.num_layers, batch, t, hkv, hd),
                             cfg.activation_dtype),
        "cross_v": jnp.zeros((cfg.num_layers, batch, t, hkv, hd),
                             cfg.activation_dtype),
    }


def whisper_cache_axes(cfg: ModelConfig) -> dict:
    ca = ("layers", "kv_batch", "kv_seq_sharded", None, None)
    return {"self": attn.kv_cache_axes(cfg, layers=True),
            "cross_k": ca, "cross_v": ca}


def whisper_prefill(cfg: ModelConfig, params: dict[str, Any],
                    batch: dict[str, jax.Array], cache: dict):
    """Encode audio + run the teacher-forced prompt, filling both caches."""
    dt = cfg.activation_dtype
    enc_out = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    x = jnp.take(params["embedding"].astype(dt), tokens, axis=0)
    pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
    x = x + pos[None]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(h, xs):
        p, self_cache = xs
        a, new_self = attn.prefill_into_cache(
            cfg, p["self_attn"], _ln(cfg, p["ln1"], h), positions, self_cache)
        h = h + a
        # cross attention + record enc K/V
        hq = _ln(cfg, p["ln2"], h)
        ck = jnp.einsum("btd,dhk->bthk", enc_out, p["cross_attn"]["wk"].astype(dt))
        cv = jnp.einsum("btd,dhk->bthk", enc_out, p["cross_attn"]["wv"].astype(dt))
        if cfg.qkv_bias:
            ck = ck + p["cross_attn"]["bk"].astype(dt)
            cv = cv + p["cross_attn"]["bv"].astype(dt)
        if cfg.kv_repeat > 1:
            ck = jnp.repeat(ck, cfg.kv_repeat, axis=2)
            cv = jnp.repeat(cv, cfg.kv_repeat, axis=2)
        c = attn.attn_forward(cfg, p["cross_attn"], hq, positions,
                              causal=False, kv_x=enc_out,
                              kv_positions=jnp.arange(enc_out.shape[1],
                                                      dtype=jnp.int32))
        h = h + c
        h = h + _mlp(cfg, p["mlp"], _ln(cfg, p["ln3"], h))
        return h, (new_self, ck, cv)

    x, (new_self, cross_k, cross_v) = lax.scan(
        body, x, (params["dec_layers"], cache["self"]))
    x = _ln(cfg, params["dec_ln"], x[:, -1:])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(dt))
    return logits, {"self": new_self, "cross_k": cross_k, "cross_v": cross_v}


def whisper_decode_step(cfg: ModelConfig, params: dict[str, Any], cache: dict,
                        tokens: jax.Array, pos: jax.Array):
    dt = cfg.activation_dtype
    x = jnp.take(params["embedding"].astype(dt), tokens, axis=0)
    posv = jnp.asarray(pos, jnp.int32)
    # sinusoidal position of the current step
    half = cfg.d_model // 2
    import math as _math
    log_ts = _math.log(10_000.0) / (half - 1)
    inv = jnp.exp(-log_ts * jnp.arange(half, dtype=jnp.float32))
    ang = posv.astype(jnp.float32) * inv
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None, :].astype(dt)
    x = x + pe

    b = x.shape[0]
    h_, hd = cfg.num_heads, cfg.hd

    def body(h, xs):
        p, self_cache, ck, cv = xs
        a, new_self = attn.attn_decode(cfg, p["self_attn"],
                                       _ln(cfg, p["ln1"], h), self_cache, pos)
        h = h + a
        hq = _ln(cfg, p["ln2"], h)
        q = jnp.einsum("bsd,dhk->bshk", hq, p["cross_attn"]["wq"].astype(dt))
        if cfg.qkv_bias:
            q = q + p["cross_attn"]["bq"].astype(dt)
        hkv = ck.shape[2]
        g = h_ // hkv
        qg = q.reshape(b, 1, hkv, g, hd)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, ck).astype(jnp.float32)
        logits = logits * (1.0 / float(hd) ** 0.5)
        probs = jax.nn.softmax(logits, axis=-1).astype(dt)
        o = jnp.einsum("bkgst,btkh->bskgh", probs, cv).reshape(b, 1, h_, hd)
        c = jnp.einsum("bshk,hkd->bsd", o, p["cross_attn"]["wo"].astype(dt))
        h = h + c
        h = h + _mlp(cfg, p["mlp"], _ln(cfg, p["ln3"], h))
        return h, new_self

    x, new_self = lax.scan(
        body, x, (params["dec_layers"], cache["self"],
                  cache["cross_k"], cache["cross_v"]))
    x = _ln(cfg, params["dec_ln"], x)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(dt))
    return logits, {"self": new_self, "cross_k": cache["cross_k"],
                    "cross_v": cache["cross_v"]}
