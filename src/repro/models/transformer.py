"""Decoder-only LM covering the dense, MoE and VLM families.

Each homogeneous run of layers is executed with ``jax.lax.scan`` over
parameters stacked along a leading ``layers`` dimension: the lowered HLO
contains one layer body per run regardless of depth, which keeps compile
time flat in depth and is the standard production pattern (MaxText et
al.). A model with ``first_dense_layers`` (deepseek-v3, moonlight) has
two runs: ``dense_layers`` with a dense MLP, then ``layers`` with the
expert layer; its cache holds one stacked tree per run under the same
names. Every other model has the single ``layers`` run and a cache that is
that run's tree. Prefill and decode carry each run's stacked cache through
its scan and update it in place, layer by layer.

Remat (activation checkpointing) wraps the scanned body in training;
``cfg.remat_policy`` names the policy.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import (
    ModelConfig,
    ParamSpec,
    maybe_remat,
    rms_norm,
    shard,
    softmax_cross_entropy,
    stack_specs,
)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def make_layer_specs(cfg: ModelConfig, *, dense: bool = False
                     ) -> dict[str, Any]:
    """One layer; ``dense`` for a leading dense layer of an MoE model."""
    specs: dict[str, Any] = {
        "ln_attn": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                             f32_at_use=True),
        "ln_mlp": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                            f32_at_use=True),
        "attn": attn.make_attn_specs(cfg),
    }
    if cfg.family == "moe" and not dense:
        specs["moe"] = mlp_mod.make_moe_specs(cfg)
    else:
        specs["mlp"] = mlp_mod.make_mlp_specs(cfg)
    return specs


def make_lm_specs(cfg: ModelConfig) -> dict[str, Any]:
    vp = cfg.padded_vocab
    n0 = cfg.first_dense_layers
    specs: dict[str, Any] = {
        "embedding": ParamSpec((vp, cfg.d_model), ("vocab", "embed")),
        "layers": stack_specs(make_layer_specs(cfg), cfg.num_layers - n0),
        "ln_final": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                              f32_at_use=True),
    }
    if n0:
        specs["dense_layers"] = stack_specs(
            make_layer_specs(cfg, dense=True), n0)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, vp), ("embed", "vocab"))
    if cfg.family == "vlm":
        specs["mm_projector"] = ParamSpec(
            (cfg.d_model, cfg.d_model), ("embed", "embed_out"))
    return specs


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _layer_forward(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pre-norm block. Returns (x, aux_loss)."""
    rm = cfg.residual_multiplier
    h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
    a = attn.attn_forward(cfg, p["attn"], h, positions, causal=True)
    x = x + rm * a
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    m, aux = _ffn(cfg, p, h)
    x = x + rm * m
    x = shard(x, "batch", "act_seq", None)
    return x, aux


def _ffn(cfg: ModelConfig, p: dict[str, Any], h: jax.Array
         ) -> tuple[jax.Array, jax.Array]:
    """The layer's MLP or expert layer; (output, aux loss)."""
    if "moe" in p:
        return mlp_mod.moe_forward(cfg, p["moe"], h)
    return mlp_mod.mlp_forward(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)


#: the stacked runs of layers, in the order they run
STACKS = ("dense_layers", "layers")


def _stack_forward(cfg: ModelConfig, params: dict[str, Any], x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    def body(carry, layer_params):
        h, aux = carry
        h, a = _layer_forward(cfg, layer_params, h, positions)
        return (h, aux + a), None

    body = maybe_remat(body, cfg.remat_policy)
    carry = (x, jnp.zeros((), jnp.float32))
    for name in STACKS:
        if name in params:
            carry, _ = lax.scan(body, carry, params[name])
    return carry


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: dict[str, Any], tokens: jax.Array
                 ) -> jax.Array:
    emb = params["embedding"].astype(cfg.activation_dtype)
    x = jnp.take(emb, tokens, axis=0)
    return x * cfg.embedding_multiplier


def lm_logits(cfg: ModelConfig, params: dict[str, Any], x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["ln_final"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embedding"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    logits = shard(logits, "batch", "act_seq", "vocab_sharded")
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# Full forward / loss
# ---------------------------------------------------------------------------

def _maybe_prepend_patches(cfg: ModelConfig, params: dict[str, Any],
                           x: jax.Array, batch: dict[str, jax.Array]):
    """VLM family: prepend (projected) precomputed patch embeddings (stub)."""
    if cfg.family != "vlm":
        return x
    patches = batch["patches"].astype(x.dtype)          # (B, P, D) stub
    proj = jnp.einsum("bpd,de->bpe", patches,
                      params["mm_projector"].astype(x.dtype))
    return jnp.concatenate([proj, x], axis=1)


def lm_forward(cfg: ModelConfig, params: dict[str, Any],
               batch: dict[str, jax.Array]) -> tuple[jax.Array, jax.Array]:
    """Returns (logits over the text region, aux_loss)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    x = _maybe_prepend_patches(cfg, params, x, batch)
    x = shard(x, "batch", "act_seq", None)
    s_total = x.shape[1]
    positions = jnp.arange(s_total, dtype=jnp.int32)
    x, aux = _stack_forward(cfg, params, x, positions)
    if cfg.family == "vlm":
        x = x[:, cfg.num_patches:]                       # loss on text only
    logits = lm_logits(cfg, params, x)
    return logits, aux


def lm_loss(cfg: ModelConfig, params: dict[str, Any],
            batch: dict[str, jax.Array]) -> tuple[jax.Array, dict[str, jax.Array]]:
    logits, aux = lm_forward(cfg, params, batch)
    loss, denom = softmax_cross_entropy(logits, batch["labels"],
                                        batch.get("mask"), cfg.vocab_size)
    total = loss + 0.01 * aux
    return total, {"ce_loss": loss, "aux_loss": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict[str, Any]:
    n0 = cfg.first_dense_layers
    if not n0:
        return attn.init_kv_cache(cfg, batch, max_len, layers=cfg.num_layers)
    return {"dense_layers": attn.init_kv_cache(cfg, batch, max_len, layers=n0),
            "layers": attn.init_kv_cache(cfg, batch, max_len,
                                         layers=cfg.num_layers - n0)}


def lm_cache_axes(cfg: ModelConfig) -> dict[str, Any]:
    axes = attn.kv_cache_axes(cfg, layers=True)
    if not cfg.first_dense_layers:
        return axes
    return {name: axes for name in STACKS}


def _run_stacks(cfg: ModelConfig, params: dict[str, Any], cache, body, x):
    """``body((h, cache), (layer_params, layer))`` over every run of
    layers. The run's whole stacked cache rides in the scan's carry, and
    ``layer`` (the layer's index, beside its parameters) tells the body
    which layer of it to write and read in place: the donated buffer stays
    one buffer, and no layer is sliced out or stacked back. Returns
    (x, the new cache)."""
    runs = dict(cache) if cfg.first_dense_layers else {"layers": cache}
    for name in STACKS:
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            (x, runs[name]), _ = lax.scan(
                lambda carry, xs: (body(carry, xs), None), (x, runs[name]),
                (params[name], np.arange(n, dtype=np.int32)))
    return x, (runs if cfg.first_dense_layers else runs["layers"])


def lm_prefill(cfg: ModelConfig, params: dict[str, Any],
               batch: dict[str, jax.Array], cache: dict[str, Any]
               ) -> tuple[jax.Array, dict[str, Any]]:
    """Run the prompt through the stack, filling the cache.

    Returns (last-position logits, cache).
    """
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    x = _maybe_prepend_patches(cfg, params, x, batch)
    x = shard(x, "batch", "act_seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_attn"], cfg.norm_eps)
        a, cache = attn.prefill_into_cache(
            cfg, layer_params["attn"], hn, positions, cache, layer=layer)
        h = h + cfg.residual_multiplier * a
        hn = rms_norm(h, layer_params["ln_mlp"], cfg.norm_eps)
        m, _ = _ffn(cfg, layer_params, hn)
        h = h + cfg.residual_multiplier * m
        h = shard(h, "batch", "act_seq", None)
        return h, cache

    body = maybe_remat(body, cfg.remat_policy)
    x, new_cache = _run_stacks(cfg, params, cache, body, x)
    logits = lm_logits(cfg, params, x[:, -1:])
    return logits, new_cache


def lm_decode_step(cfg: ModelConfig, params: dict[str, Any],
                   cache: dict[str, Any], tokens: jax.Array, pos: jax.Array
                   ) -> tuple[jax.Array, dict[str, Any]]:
    """One decode step. tokens: (B, 1); pos: scalar current position."""
    x = embed_tokens(cfg, params, tokens)
    x = shard(x, "batch", None, None)

    def body(carry, xs):
        h, cache = carry
        layer_params, layer = xs
        hn = rms_norm(h, layer_params["ln_attn"], cfg.norm_eps)
        a, cache = attn.attn_decode(cfg, layer_params["attn"], hn, cache, pos,
                                    layer=layer)
        h = h + cfg.residual_multiplier * a
        hn = rms_norm(h, layer_params["ln_mlp"], cfg.norm_eps)
        m, _ = _ffn(cfg, layer_params, hn)
        h = h + cfg.residual_multiplier * m
        return h, cache

    x, new_cache = _run_stacks(cfg, params, cache, body, x)
    logits = lm_logits(cfg, params, x)
    return logits, new_cache
