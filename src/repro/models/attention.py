"""Multi-head / grouped-query attention for all architecture families.

Three interchangeable implementations (``cfg.attn_impl``):

* ``direct``  — one einsum; right choice for short sequences / smoke tests.
* ``chunked`` — memory-efficient online-softmax scan over KV blocks
                (flash-attention recurrence in pure JAX). This keeps the
                lowered HLO's temporary footprint ``O(S · kv_block)`` instead
                of ``O(S²)`` for long prefills.
* ``pallas``  — the fused Pallas TPU kernel (kernels/flash_attention).

Decode reads a KV cache (``init_kv_cache``) stored in the activation
dtype: one stacked buffer per run of layers, written in place, read by
the masked einsum or the flash-decode kernel (``cfg.decode_impl``).
Latent attention (MLA) caches one latent row per position instead.

GQA KV-head *physical repetition*: when the KV-head count does not divide
the model axis, k/v activations (and the KV cache) are tiled ``kv_repeat``
times so they shard. Weights keep the architecture's true KV-head count, so
the math is unchanged — the repeat is purely a layout transformation.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import (
    ModelConfig,
    ParamSpec,
    active_mesh,
    logical_to_spec,
    rms_norm,
    rope,
    shard,
)

NEG_INF = -2.0e30


# ---------------------------------------------------------------------------
# Parameter specification
# ---------------------------------------------------------------------------

def make_attn_specs(cfg: ModelConfig, *, cross: bool = False) -> dict[str, ParamSpec]:
    if cfg.kv_lora_rank:
        return make_mla_specs(cfg)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    specs: dict[str, ParamSpec] = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads_w", "head_dim")),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads_w", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((hkv, hd), ("kv_heads_w", "head_dim"), init="zeros")
        specs["bv"] = ParamSpec((hkv, hd), ("kv_heads_w", "head_dim"), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones",
                                    f32_at_use=True)
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones",
                                    f32_at_use=True)
    return specs


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                 kv_x: jax.Array | None = None):
    """Project to q, k, v; apply qk-norm; tile kv heads to kv_heads_eff."""
    dt = x.dtype
    kv_in = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_in, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_in, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.kv_repeat > 1:
        # Physical tiling for shardability; consecutive-group semantics match
        # the (Hkv, G) query grouping below.
        k = jnp.repeat(k, cfg.kv_repeat, axis=2)
        v = jnp.repeat(v, cfg.kv_repeat, axis=2)
    return q, k, v


def _shard_qkv(cfg: ModelConfig, q, k, v):
    if cfg.attn_sharding == "heads":
        q = shard(q, "batch", None, "heads_sharded", None)
        k = shard(k, "batch", None, "kv_heads_sharded", None)
        v = shard(v, "batch", None, "kv_heads_sharded", None)
    else:  # sequence/context parallel: shard q along seq, kv batch-only
        q = shard(q, "batch", "seq_sharded", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
    return q, k, v


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------

def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: int, kv_len: jax.Array | None) -> jax.Array:
    """(Sq, Skv) additive bias in fp32. kv_len masks out unwritten cache."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    if kv_len is not None:
        ok &= (k_pos < kv_len)[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(logits / cap)
    return logits


# ---------------------------------------------------------------------------
# Core attention math (grouped)
# ---------------------------------------------------------------------------

def _direct_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, causal,
                      window, kv_len=None) -> jax.Array:
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = cfg.attention_multiplier or (1.0 / float(hd) ** 0.5)
    qg = q.reshape(b, sq, hkv, g, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    logits = _softcap(logits, cfg.attn_softcap)
    logits = logits + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                                 kv_len=kv_len)[None, None, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def _chunked_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, causal,
                       window, kv_len=None) -> jax.Array:
    """Online-softmax scan over KV blocks; O(Sq·kv_block) temporaries."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    blk = min(cfg.attn_kv_block, skv)
    while skv % blk:
        blk //= 2
    nblk = skv // blk
    scale = cfg.attention_multiplier or (1.0 / float(hd) ** 0.5)
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, g, hd)

    def body(carry, j):
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, j * blk, blk, axis=1)
        vb = lax.dynamic_slice_in_dim(v, j * blk, blk, axis=1)
        kp = lax.dynamic_slice_in_dim(k_pos, j * blk, blk, axis=0)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(q.dtype), kb)
        logits = logits.astype(jnp.float32)
        logits = _softcap(logits, cfg.attn_softcap)
        logits = logits + _mask_bias(q_pos, kp, causal=causal, window=window,
                                     kv_len=kv_len)[None, None, None]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkh->bkgsh", p.astype(q.dtype), vb)
        acc_new = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nblk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (b, hkv, g, sq, hd_v) -> (b, sq, h, hd_v)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, v.shape[-1])
    return out.astype(q.dtype)

    # NOTE: scale was already folded into qg before the scan.


def _pallas_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, causal,
                      window, kv_len=None) -> jax.Array:
    from repro.kernels.flash_attention import ops as fa_ops

    if kv_len is not None or not causal:
        # Cache-masked / non-causal paths stay on the chunked implementation.
        return _chunked_attention(cfg, q, k, v, q_pos, k_pos, causal=causal,
                                  window=window, kv_len=kv_len)
    scale = cfg.attention_multiplier or (1.0 / float(q.shape[-1]) ** 0.5)
    return fa_ops.flash_attention(
        q, k, v, causal=True, window=window, scale=scale,
        softcap=cfg.attn_softcap, q_offset=q_pos[0],
        block_q=cfg.attn_q_block, block_kv=cfg.attn_kv_block,
    )


_IMPLS = {
    "direct": _direct_attention,
    "chunked": _chunked_attention,
    "pallas": _pallas_attention,
}


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def attn_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, *, causal: bool = True,
                 window: int = 0, kv_x: jax.Array | None = None,
                 kv_positions: jax.Array | None = None) -> jax.Array:
    """Full (train/prefill) attention. x: (B, S, D)."""
    if cfg.kv_lora_rank:
        return mla_forward(cfg, p, x, positions)
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if cfg.use_rope and kv_x is None:
        q, k = rope(q, k, positions, cfg.rope_theta)
    q, k, v = _shard_qkv(cfg, q, k, v)
    k_pos = positions if kv_positions is None else kv_positions
    impl = _IMPLS[cfg.attn_impl]
    out = impl(cfg, q, k, v, positions, k_pos, causal=causal, window=window)
    if cfg.attn_sharding == "heads":
        out = shard(out, "batch", None, "heads_sharded", None)
    else:
        out = shard(out, "batch", "seq_sharded", None, None)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  *, layers: int | None = None) -> dict[str, Any]:
    """Cache pytree (ShapeDtypeStruct-compatible via jax.eval_shape).

    Entries are stored in the activation dtype. Positions are the last
    axis: per-head k and v are (B, Hkv, hd, S), latent rows (B, C, S).
    The decode kernel
    streams (hd, block) tiles of a head without a transpose, and on the
    TPU the buffer's default layout is the row-major one its call takes
    (with hd = 64 or C = 576 last, XLA would lay positions out minor and
    the call would relayout the whole cache). ``layers`` stacks a leading
    layer axis, which prefill and decode carry whole and update in place
    (the ``layer`` arguments below).
    """
    lead = (batch,) if layers is None else (layers, batch)
    if cfg.kv_lora_rank:
        return {"latent": jnp.zeros(lead + (cfg.latent_width, max_len),
                                    cfg.activation_dtype)}
    shape = lead + (cfg.kv_heads_eff, cfg.hd, max_len)
    return {
        "k": jnp.zeros(shape, cfg.activation_dtype),
        "v": jnp.zeros(shape, cfg.activation_dtype),
    }


def kv_cache_axes(cfg: ModelConfig, *, layers: bool = True) -> dict[str, tuple]:
    """Logical axes of the cache (leading 'layers' when stacked)."""
    lead = ("layers",) if layers else ()
    if cfg.kv_lora_rank:
        # one latent row per position, shared by every head
        seq = None if cfg.attn_sharding == "heads" else "kv_seq_sharded"
        return {"latent": lead + ("kv_batch", None, seq)}
    if cfg.attn_sharding == "heads":
        ax = lead + ("kv_batch", "kv_heads_sharded", None, None)
    else:
        ax = lead + ("kv_batch", None, None, "kv_seq_sharded")
    return {"k": ax, "v": ax}


def _stack_of_one(cache: dict[str, jax.Array]) -> dict[str, jax.Array]:
    return jax.tree.map(lambda t: t[None], cache)


def _only_layer(cache: dict[str, jax.Array]) -> dict[str, jax.Array]:
    return jax.tree.map(lambda t: t[0], cache)


def _put_rows(buf: jax.Array, rows: jax.Array, layer, pos) -> jax.Array:
    """Write ``rows``, a layer's (B, ..., S') update, into layer ``layer``
    of the stacked cache ``buf`` (L, B, ..., S) in place, at positions
    ``pos`` on: a scalar, or one position per row, then written row by row
    with one ``dynamic_update_slice`` each (a scatter of the rows is not
    kept in place: it copies the whole stack), so no layer is rebuilt."""
    rows = rows.astype(buf.dtype)[None]
    start = [layer] + [0] * (buf.ndim - 1)
    if getattr(pos, "ndim", 0) == 0:
        start[-1] = pos
        return lax.dynamic_update_slice(buf, rows, start)
    for b in range(rows.shape[1]):
        start[1], start[-1] = b, pos[b]
        buf = lax.dynamic_update_slice(buf, rows[:, b:b + 1], start)
    return buf


def _cache_write(cache: dict[str, jax.Array], k: jax.Array, v: jax.Array,
                 layer, pos) -> dict[str, jax.Array]:
    """Write k/v as projected, (B, S', Hkv, hd), from position ``pos`` on
    into layer ``layer`` of the stacked cache (ring order handled
    upstream).

    ``pos`` may be a scalar (all rows at the same depth) or a (B,) vector —
    the continuous-batching case where every slot sits at its own position.
    """
    return {name: _put_rows(cache[name], rows.transpose(0, 2, 3, 1), layer,
                            pos)
            for name, rows in {"k": k, "v": v}.items()}


def _cache_read(cache: dict[str, jax.Array], layer):
    """Layer ``layer``'s k and v, (B, Hkv, hd, S)."""
    return tuple(lax.dynamic_index_in_dim(cache[name], layer, keepdims=False)
                 for name in ("k", "v"))


def attn_decode(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                cache: dict[str, jax.Array], pos: jax.Array, *,
                layer=None, window: int = 0
                ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 current position,
    or a (B,) int32 vector of *per-row* positions (continuous batching —
    each slot writes its k/v at, and attends up to, its own depth).

    ``cache`` is one layer's tree, or with ``layer`` (the layer's index)
    the model's stacked tree: the new row is written into that layer of
    the stack in place and the kernel reads the layer from the stack.

    For ``window > 0`` the cache is a ring buffer of length ``window`` —
    entries are written at ``pos % window`` and masked by recency. Ring
    buffers require a scalar ``pos`` (all rows advance in lockstep).
    """
    if layer is None:
        out, cache = attn_decode(cfg, p, x, _stack_of_one(cache), pos,
                                 layer=0, window=window)
        return out, _only_layer(cache)
    if cfg.kv_lora_rank:
        return mla_decode(cfg, p, x, cache, pos, layer)
    b = x.shape[0]
    per_row = getattr(pos, "ndim", 0) == 1
    if per_row and window > 0:
        raise ValueError("per-row decode positions are incompatible with "
                         "ring-buffer (windowed) KV caches")
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        if per_row:
            posv = pos.astype(jnp.int32)[:, None]          # (B, 1)
        else:
            posv = jnp.full((1,), pos, jnp.int32)[None, :]  # (1, 1)
        q, k = rope(q, k, posv, cfg.rope_theta)

    max_len = cache["k"].shape[-1]
    write_pos = (pos % window) if window > 0 else pos
    cache = _cache_write(cache, k, v, layer, write_pos)
    axes = kv_cache_axes(cfg)
    cache = {name: shard(buf, *axes[name]) for name, buf in cache.items()}

    # decode activations follow the cache's batch sharding (kv_batch)
    if cfg.attn_sharding == "heads":
        q = shard(q, "kv_batch", None, "heads_sharded", None)
    else:
        q = shard(q, "kv_batch", None, None, None)

    hkv = cache["k"].shape[2]
    h = q.shape[2]
    g = h // hkv
    hd = q.shape[-1]
    scale = cfg.attention_multiplier or (1.0 / float(hd) ** 0.5)

    # Flash-decode Pallas kernel path: ragged per-row lengths land directly
    # on the kernel's scalar-prefetch lens argument, and it reads the layer
    # from the stack. Ring buffers and soft-capping stay on the
    # masked-einsum path below.
    if runs_decode_kernel(cfg) and window == 0:
        kv_len = (pos if per_row else jnp.broadcast_to(pos, (b,))) + 1
        out = _decode_kernel(cfg, q[:, 0], cache["k"], cache["v"],
                             kv_len.astype(jnp.int32), layer, float(scale))
        out = out[:, None]                                  # (B, 1, H, hd)
        out = shard(out, "kv_batch", None, "heads_sharded", None)
        dt = x.dtype
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache

    ck, cv = _cache_read(cache, layer)
    # slot -> absolute position (ring buffers wrap)
    slots = jnp.arange(max_len, dtype=jnp.int32)
    if window > 0:
        cycle = (pos // window) * window
        k_pos = jnp.where(slots <= (pos % window), cycle + slots,
                          cycle - window + slots)
        valid = (k_pos >= 0) & (k_pos > pos - window) & (k_pos <= pos)
    elif per_row:
        valid = slots[None, :] <= pos[:, None]              # (B, Smax)
    else:
        valid = slots <= pos

    qg = q.reshape(b, 1, hkv, g, hd)
    logits = jnp.einsum("bskgh,bkht->bkgst", qg, ck).astype(jnp.float32) * scale
    logits = _softcap(logits, cfg.attn_softcap)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    if per_row:
        logits = logits + bias[:, None, None, None, :]
    else:
        logits = logits + bias[None, None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bkht->bskgh", probs, cv).reshape(b, 1, h, hd)
    out = shard(out, "kv_batch", None, "heads_sharded", None)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache


def runs_decode_kernel(cfg: ModelConfig) -> bool:
    """Whether decode runs the dense flash-decode kernel: the Pallas
    decode path over per-head K/V (not a latent cache), without
    soft-capping. (A ring-buffer layer keeps the masked einsum too.)"""
    return (cfg.decode_impl == "pallas" and not cfg.kv_lora_rank
            and not cfg.attn_softcap)


def decode_kernel_block(cfg: ModelConfig, k: jax.Array) -> int:
    """Positions in each block that the dense decode kernel reads of the
    stacked K cache ``k`` (L, B, Hkv, hd, Smax), 0 where decode does not
    run it. Under a mesh each shard's block holds its share of the heads
    and may be longer."""
    if not runs_decode_kernel(cfg):
        return 0
    from repro.kernels.decode_attention.kernel import kv_block
    _, _, hkv, hd, smax = k.shape
    return kv_block(hkv, hd, smax, cfg.attn_kv_block, k.dtype.itemsize)


def _decode_kernel(cfg: ModelConfig, q, ck, cv, kv_len, layer, scale: float):
    """The flash-decode kernel over the stacked cache ``ck``/``cv``
    (L, B, Hkv, hd, S) at ``layer``; under a mesh, run per shard via
    shard_map.

    A Pallas kernel cannot be partitioned automatically, so with axis
    rules active it runs on each device's block of the cache: batch rows
    over ``kv_batch``, KV heads (and their query-head groups) over
    ``kv_heads_sharded``. A cache sharded along the sequence needs a
    cross-shard softmax merge that does not exist yet; that case keeps the
    bare call, which the TPU compiler refuses (the CPU interpreter lowers
    it to plain HLO, which GSPMD partitions).
    """
    from repro.kernels.decode_attention import ops as da_ops

    def call(q, ck, cv, kv_len, layer):
        return da_ops.decode_attention(q, ck, cv, kv_len, layer=layer,
                                       scale=scale,
                                       block_kv=cfg.attn_kv_block)

    layer = jnp.asarray(layer, jnp.int32)
    mesh = active_mesh()
    if mesh is None:
        return call(q, ck, cv, kv_len, layer)
    _, batch, heads, _, seq = logical_to_spec(kv_cache_axes(cfg)["k"])
    seq_axes = (seq,) if isinstance(seq, str) else tuple(seq or ())
    if any(mesh.shape[a] > 1 for a in seq_axes):
        return call(q, ck, cv, kv_len, layer)
    kv_spec = P(None, batch, heads, None, None)
    q_spec = P(batch, heads, None)
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(batch), P()),
                         out_specs=q_spec, check_vma=False)(
                             q, ck, cv, kv_len, layer)


def prefill_into_cache(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                       positions: jax.Array, cache: dict[str, jax.Array], *,
                       layer=None, window: int = 0):
    """Prefill attention that also populates the cache for later decode:
    one layer's cache, or with ``layer`` that layer of the stacked cache,
    written in place."""
    if layer is None:
        out, cache = prefill_into_cache(cfg, p, x, positions,
                                        _stack_of_one(cache), layer=0,
                                        window=window)
        return out, _only_layer(cache)
    if cfg.kv_lora_rank:
        return mla_prefill(cfg, p, x, positions, cache, layer)
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        q, k = rope(q, k, positions, cfg.rope_theta)
    q, k, v = _shard_qkv(cfg, q, k, v)
    s = x.shape[1]
    ks, vs = k, v
    if window > 0:
        # keep the last `window` entries in ring order: slot (pos % window);
        # since we write a contiguous tail, roll so that slot indices line up
        w = min(window, s)
        idx = (jnp.arange(w) + (s - w)) % window
        order = jnp.argsort(idx)
        ks, vs = k[:, s - w:][:, order], v[:, s - w:][:, order]
    cache = _cache_write(cache, ks, vs, layer, 0)
    impl = _IMPLS[cfg.attn_impl]
    out = impl(cfg, q, k, v, positions[0] if positions.ndim > 1 else positions,
               positions[0] if positions.ndim > 1 else positions,
               causal=True, window=window)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache


# ---------------------------------------------------------------------------
# Latent attention (MLA: deepseek-v2/v3 with q_lora_rank null)
# ---------------------------------------------------------------------------
#
#   q            = x W_q                         H x (nope + rope)
#   [c, k_pe]    = x W_kv_a; c = RMSNorm(c)      r + rope, k_pe one head
#   [k_nope, v]  = c W_kv_b                      H x (nope + v)
#   rope on q_pe and k_pe over interleaved pairs (none where use_rope is
#   off: kimi_linear's NoPE layers); scale (nope + rope)^-1/2
#
# The cache holds the latent row [c, k_pe] of each position, not per-head
# keys and values. Prefill and training expand c into k and v; decode
# absorbs W_kv_b into the query and the output instead: q_nope W_uk^T
# scores against c, and the softmax-weighted sum of c goes through W_uv.

def make_mla_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    return {
        "wq": ParamSpec((d, h, nope + rope_d), ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, r + rope_d), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), init="ones", f32_at_use=True),
        "wkv_b": ParamSpec((r, h, nope + vd), (None, "heads", "head_dim")),
        "wo": ParamSpec((h, vd, d), ("heads", "head_dim", "embed")),
    }


def _deinterleave(x: jax.Array) -> jax.Array:
    """(x0, x1, x2, x3, ...) -> (x0, x2, ..., x1, x3, ...): the published
    code's rotary pairs are interleaved; de-interleaved they are the two
    halves that ``rope`` rotates."""
    *lead, n = x.shape
    return x.reshape(*lead, n // 2, 2).swapaxes(-1, -2).reshape(*lead, n)


def _mla_project(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array):
    """q_nope (B,S,H,nope), q_pe (B,S,H,rope), latent rows (B,S,r+rope):
    the normed latent and the rope key, both parts rotated unless
    ``cfg.use_rope`` is off (kimi_linear's ``mla_use_nope``: the shared
    key and q_pe are scored as projected, and ``positions`` is unused)."""
    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(dt))
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.latent_norm_eps)
    if cfg.use_rope:
        q_pe, k_pe = rope(_deinterleave(q[..., nope:]),
                          _deinterleave(kv[..., None, r:]), positions,
                          cfg.rope_theta)
    else:
        q_pe, k_pe = q[..., nope:], kv[..., None, r:]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe[:, :, 0]], -1)


def _mla_scale(cfg: ModelConfig) -> float:
    return 1.0 / float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5


def _mla_attend(cfg: ModelConfig, p, q_nope, q_pe, latent, positions):
    """Expanded causal attention over a whole sequence; (B, S, D)."""
    dt = q_nope.dtype
    nope, r, h = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.num_heads
    kv = jnp.einsum("bsr,rhk->bshk", latent[..., :r], p["wkv_b"].astype(dt))
    k_pe = jnp.broadcast_to(latent[..., None, r:],
                            (*latent.shape[:2], h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    q, k, v = _shard_qkv(cfg, q, k, v)
    # scale: 1/sqrt(q's width), nope + rope (no attention multiplier)
    out = _IMPLS[cfg.attn_impl](cfg, q, k, v, positions, positions,
                                causal=True, window=0)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def mla_forward(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Full (train/prefill) latent attention. x: (B, S, D)."""
    with jax.named_scope("mla"):
        q_nope, q_pe, latent = _mla_project(cfg, p, x, positions)
        return _mla_attend(cfg, p, q_nope, q_pe, latent, positions)


def mla_prefill(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                positions: jax.Array, cache: dict[str, jax.Array], layer):
    """Prefill that writes the prompt's latent rows into layer ``layer``
    of the stacked cache."""
    with jax.named_scope("mla"):
        q_nope, q_pe, latent = _mla_project(cfg, p, x, positions)
        cache = {"latent": _put_rows(cache["latent"], latent.swapaxes(1, 2),
                                     layer, 0)}
        pos = positions[0] if positions.ndim > 1 else positions
        return _mla_attend(cfg, p, q_nope, q_pe, latent, pos), cache


def mla_decode(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
               cache: dict[str, jax.Array], pos: jax.Array, layer):
    """One-token absorbed decode against layer ``layer`` of the stacked
    latent cache, written and read in place. x: (B, 1, D); pos: scalar,
    or (B,) per-row positions (continuous batching)."""
    b = x.shape[0]
    dt = x.dtype
    per_row = getattr(pos, "ndim", 0) == 1
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        posv = (pos.astype(jnp.int32)[:, None] if per_row
                else jnp.full((1, 1), pos, jnp.int32))
        q_nope, q_pe, latent = _mla_project(cfg, p, x, posv)
        buf = _put_rows(cache["latent"], latent.swapaxes(1, 2), layer, pos)
        buf = shard(buf, "layers", "kv_batch", None, None)
        wkv_b = p["wkv_b"].astype(dt)
        q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], wkv_b[..., :nope])
        q = jnp.concatenate([q_lat, q_pe[:, 0]], -1)        # (B, H, r+rope)
        kv_len = (pos if per_row else jnp.broadcast_to(pos, (b,))) + 1
        if cfg.decode_impl == "pallas":
            from repro.kernels.decode_attention import ops as da_ops
            o = da_ops.latent_decode_attention(
                q, buf, kv_len.astype(jnp.int32), scale=_mla_scale(cfg),
                value_dim=r, layer=layer, block_kv=cfg.attn_kv_block)
        else:
            rows = lax.dynamic_index_in_dim(buf, layer, keepdims=False)
            logits = jnp.einsum("bhc,bct->bht", q, rows,
                                preferred_element_type=jnp.float32)
            valid = jnp.arange(rows.shape[-1])[None, :] < kv_len[:, None]
            logits = jnp.where(valid[:, None, :], logits * _mla_scale(cfg),
                               NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(dt)
            o = jnp.einsum("bht,brt->bhr", probs, rows[:, :r])
        o = jnp.einsum("bhr,rhv->bhv", o.astype(dt), wkv_b[..., nope:])
        out = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(dt))
    return out[:, None], {"latent": buf}
