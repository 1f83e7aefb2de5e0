"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention.

Layer pattern (assignment: "RG-LRU + local attn, 1:2") is the Griffin
``(recurrent, recurrent, local-attention)`` repeating unit. The 26-layer
stack is *unrolled* (heterogeneous blocks; the model is small so compile cost
is negligible next to the scanned 95-layer stacks).

The RG-LRU recurrence ``h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)``
is evaluated blockwise: a sequential ``lax.scan`` over time blocks with an
``associative_scan`` inside each block — the exact structure the Pallas
kernel (kernels/rglru_scan) implements on TPU, and sub-quadratic in sequence
length (this is why this arch runs the ``long_500k`` cell).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
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
)

RG_LRU_C = 8.0


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def make_rglru_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    d, dr = cfg.d_model, cfg.d_rnn or cfg.d_model
    w = cfg.conv_width
    nb = cfg.rnn_blocks
    blk = dr // nb
    # Gates are block-diagonal (nb blocks) so the gate matmuls shard over the
    # model axis with zero communication. The official RecurrentGemma uses
    # num_heads(=10) diagonal blocks; we use 16 to align blocks with the
    # model-axis shards.
    return {
        "ln": ParamSpec((d,), ("embed",), init="ones"),
        "w_y": ParamSpec((d, dr), ("embed", "rnn_tp")),        # gate branch
        "w_x": ParamSpec((d, dr), ("embed", "rnn_tp")),        # recurrence branch
        "conv_w": ParamSpec((w, dr), (None, "rnn_tp")),
        "conv_b": ParamSpec((dr,), ("rnn_tp",), init="zeros"),
        "w_a": ParamSpec((nb, blk, blk), ("rnn_blocks", None, None)),
        "b_a": ParamSpec((dr,), ("rnn_tp",), init="zeros"),
        "w_i": ParamSpec((nb, blk, blk), ("rnn_blocks", None, None)),
        "b_i": ParamSpec((dr,), ("rnn_tp",), init="zeros"),
        "lam": ParamSpec((dr,), ("rnn_tp",), init="rglru_lambda"),
        "w_o": ParamSpec((dr, d), ("rnn_tp", "embed")),
    }


def make_attn_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    return {
        "ln": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn.make_attn_specs(cfg),
    }


def make_mlp_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    return {
        "ln": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        "mlp": mlp_mod.make_mlp_specs(cfg),
    }


def layer_kinds(cfg: ModelConfig) -> list[str]:
    pattern = cfg.block_pattern or ("rglru", "rglru", "attn")
    return [pattern[i % len(pattern)] for i in range(cfg.num_layers)]


def make_griffin_specs(cfg: ModelConfig) -> dict[str, Any]:
    layers = []
    for kind in layer_kinds(cfg):
        if kind == "rglru":
            layers.append({"kind_rglru": make_rglru_block_specs(cfg),
                           "mlp_block": make_mlp_block_specs(cfg)})
        else:
            layers.append({"kind_attn": make_attn_block_specs(cfg),
                           "mlp_block": make_mlp_block_specs(cfg)})
    return {
        "embedding": ParamSpec((cfg.padded_vocab, cfg.d_model), ("vocab", "embed")),
        "layers": layers,
        "ln_final": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
    }


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------

def rglru_gates(p: dict[str, jax.Array], xr: jax.Array):
    """Gate computation shared by scan paths. xr: (..., dr) post-conv input.

    Gates are block-diagonal: w_a/w_i have shape (nb, blk, blk)."""
    f32 = jnp.float32
    nb, blk, _ = p["w_a"].shape
    xb = xr.astype(f32).reshape(*xr.shape[:-1], nb, blk)
    ra = jnp.einsum("...bk,bko->...bo", xb, p["w_a"].astype(f32))
    ia = jnp.einsum("...bk,bko->...bo", xb, p["w_i"].astype(f32))
    ra = ra.reshape(xr.shape) + p["b_a"].astype(f32)
    ia = ia.reshape(xr.shape) + p["b_i"].astype(f32)
    r = jax.nn.sigmoid(ra)
    i = jax.nn.sigmoid(ia)
    log_a = -RG_LRU_C * r * jax.nn.softplus(p["lam"].astype(f32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    gated_x = i * xr.astype(f32)
    return a, beta * gated_x


def rglru_scan_ref(a: jax.Array, bx: jax.Array, h0: jax.Array,
                   block: int = 256) -> tuple[jax.Array, jax.Array]:
    """Blocked linear scan. a, bx: (B, S, dr) fp32; h0: (B, dr).

    Returns (h over all t, final h). Outer sequential scan over time blocks,
    inner associative_scan — mirrors the Pallas kernel structure.
    """
    b, s, dr = a.shape
    blk = min(block, s)
    while s % blk:
        blk //= 2
    n = s // blk
    a_b = a.reshape(b, n, blk, dr).swapaxes(0, 1)   # (n, B, blk, dr)
    x_b = bx.reshape(b, n, blk, dr).swapaxes(0, 1)

    def combine(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, x2 + a2 * x1

    def body(h, xs):
        ab, xb = xs
        a_acc, x_acc = lax.associative_scan(combine, (ab, xb), axis=1)
        hs = x_acc + a_acc * h[:, None, :]
        return hs[:, -1, :], hs

    h_last, hs = lax.scan(body, h0, (a_b, x_b))
    hs = hs.swapaxes(0, 1).reshape(b, s, dr)
    return hs, h_last


def _causal_conv(p: dict[str, jax.Array], x: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv over time. x: (B, S, dr); state: (B, w-1, dr)."""
    w = p["conv_w"].shape[0]
    dt = x.dtype
    if state is None:
        pad = jnp.zeros((x.shape[0], w - 1, x.shape[2]), dt)
    else:
        pad = state.astype(dt)
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(w):
        out = out + xp[:, j:j + x.shape[1], :].astype(jnp.float32) * \
            p["conv_w"][j].astype(jnp.float32)
    out = out + p["conv_b"].astype(jnp.float32)
    new_state = xp[:, xp.shape[1] - (w - 1):, :]
    return out.astype(dt), new_state


def rglru_block_forward(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                        state: dict[str, jax.Array] | None = None,
                        use_pallas: bool = False):
    """Full-sequence recurrent block. Returns (out, new_state)."""
    dt = x.dtype
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    y = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", h, p["w_y"].astype(dt)))
    xr = jnp.einsum("bsd,dr->bsr", h, p["w_x"].astype(dt))
    xr = shard(xr, "batch", "act_seq_rnn", "rnn_sharded")
    conv_state = state["conv"] if state is not None else None
    xr, new_conv = _causal_conv(p, xr, conv_state)
    a, bx = rglru_gates(p, xr)
    h0 = (state["h"].astype(jnp.float32) if state is not None
          else jnp.zeros((x.shape[0], a.shape[-1]), jnp.float32))
    if use_pallas:
        from repro.kernels.rglru_scan import ops as rg_ops
        hs, h_last = rg_ops.rglru_scan(a, bx, h0)
    else:
        hs, h_last = rglru_scan_ref(a, bx, h0)
    hs = hs.astype(dt) * y
    out = jnp.einsum("bsr,rd->bsd", hs, p["w_o"].astype(dt))
    new_state = {"h": h_last, "conv": new_conv}
    return out, new_state


def rglru_block_decode(cfg: ModelConfig, p: dict[str, Any], x: jax.Array,
                       state: dict[str, jax.Array]):
    """Single-token step. x: (B, 1, D)."""
    out, new_state = rglru_block_forward(cfg, p, x, state)
    return out, new_state


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _mlp_sub(cfg: ModelConfig, p: dict[str, Any], x: jax.Array) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    return x + mlp_mod.mlp_forward(cfg, p["mlp"], h)


def griffin_forward(cfg: ModelConfig, params: dict[str, Any],
                    batch: dict[str, jax.Array]) -> jax.Array:
    tokens = batch["tokens"]
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype), tokens, axis=0)
    x = x * (cfg.d_model ** 0.5)      # gemma-style embedding scaling
    x = shard(x, "batch", "act_seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    kinds = layer_kinds(cfg)

    def layer(x, p, kind):
        if kind == "rglru":
            out, _ = rglru_block_forward(cfg, p["kind_rglru"], x,
                                         use_pallas=cfg.use_pallas)
            x = x + out
        else:
            h = rms_norm(x, p["kind_attn"]["ln"], cfg.norm_eps)
            x = x + attn.attn_forward(cfg, p["kind_attn"]["attn"], h, positions,
                                      causal=True, window=cfg.local_window)
        return _mlp_sub(cfg, p["mlp_block"], x)

    for i, (p, kind) in enumerate(zip(params["layers"], kinds)):
        fn = maybe_remat(lambda x, p, k=kind: (layer(x, p, k), None),
                         cfg.remat_policy)
        x, _ = fn(x, p)
    x = rms_norm(x, params["ln_final"], cfg.norm_eps)
    emb = params["embedding"].astype(x.dtype)
    logits = jnp.einsum("bsd,vd->bsv", x, emb)   # tied head
    return shard(logits, "batch", "act_seq", "vocab_sharded")


def griffin_loss(cfg: ModelConfig, params: dict[str, Any],
                 batch: dict[str, jax.Array]):
    logits = griffin_forward(cfg, params, batch)
    loss, denom = softmax_cross_entropy(
        logits, batch["labels"], batch.get("mask"), cfg.vocab_size)
    return loss, {"ce_loss": loss, "tokens": denom,
                  "aux_loss": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_griffin_state(cfg: ModelConfig, batch: int, max_len: int) -> list[dict]:
    dr = cfg.d_rnn or cfg.d_model
    states: list[dict] = []
    for kind in layer_kinds(cfg):
        if kind == "rglru":
            states.append({
                "h": jnp.zeros((batch, dr), jnp.float32),
                "conv": jnp.zeros((batch, cfg.conv_width - 1, dr),
                                  cfg.activation_dtype),
            })
        else:
            w = min(cfg.local_window or max_len, max_len)
            states.append(attn.init_kv_cache(cfg, batch, w))
    return states


def griffin_state_axes(cfg: ModelConfig) -> list[dict]:
    axes: list[dict] = []
    for kind in layer_kinds(cfg):
        if kind == "rglru":
            axes.append({"h": ("batch", "rnn_sharded"),
                         "conv": ("batch", None, "rnn_sharded")})
        else:
            axes.append(attn.kv_cache_axes(cfg, layers=False))
    return axes


def griffin_prefill(cfg: ModelConfig, params: dict[str, Any],
                    batch: dict[str, jax.Array], states: list[dict]):
    tokens = batch["tokens"]
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype), tokens, axis=0)
    x = x * (cfg.d_model ** 0.5)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    kinds = layer_kinds(cfg)
    new_states: list[dict] = []
    for p, kind, st in zip(params["layers"], kinds, states):
        if kind == "rglru":
            out, ns = rglru_block_forward(cfg, p["kind_rglru"], x,
                                          use_pallas=cfg.use_pallas)
            x = x + out
        else:
            h = rms_norm(x, p["kind_attn"]["ln"], cfg.norm_eps)
            a, ns = attn.prefill_into_cache(cfg, p["kind_attn"]["attn"], h,
                                            positions, st,
                                            window=cfg.local_window)
            x = x + a
        x = _mlp_sub(cfg, p["mlp_block"], x)
        new_states.append(ns)
    x = rms_norm(x, params["ln_final"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x[:, -1:],
                        params["embedding"].astype(x.dtype))
    return logits, new_states


def griffin_decode_step(cfg: ModelConfig, params: dict[str, Any],
                        states: list[dict], tokens: jax.Array, pos: jax.Array):
    x = jnp.take(params["embedding"].astype(cfg.activation_dtype), tokens, axis=0)
    x = x * (cfg.d_model ** 0.5)
    kinds = layer_kinds(cfg)
    new_states: list[dict] = []
    for p, kind, st in zip(params["layers"], kinds, states):
        if kind == "rglru":
            out, ns = rglru_block_decode(cfg, p["kind_rglru"], x, st)
            x = x + out
        else:
            h = rms_norm(x, p["kind_attn"]["ln"], cfg.norm_eps)
            a, ns = attn.attn_decode(cfg, p["kind_attn"]["attn"], h, st, pos,
                                     window=cfg.local_window)
            x = x + a
        x = _mlp_sub(cfg, p["mlp_block"], x)
        new_states.append(ns)
    x = rms_norm(x, params["ln_final"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(x.dtype))
    return logits, new_states
