"""Multi-head / grouped-query attention for all architecture families.

Three interchangeable implementations (``cfg.attn_impl``):

* ``direct``  — one einsum; right choice for short sequences / smoke tests.
* ``chunked`` — memory-efficient online-softmax scan over KV blocks
                (flash-attention recurrence in pure JAX). This keeps the
                lowered HLO's temporary footprint ``O(S · kv_block)`` instead
                of ``O(S²)`` so the 32k prefill cells are roofline-sane.
* ``pallas``  — the fused Pallas TPU kernel (kernels/flash_attention).

GQA KV-head *physical repetition*: when the KV-head count does not divide
the model axis, k/v activations (and the KV cache) are tiled ``kv_repeat``
times so they shard. Weights keep the architecture's true KV-head count, so
the math is unchanged — the repeat is purely a layout transformation.
"""

from __future__ import annotations

import functools
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
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
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
    return out.reshape(b, sq, h, hd)


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
    a0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nblk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (b, hkv, g, sq, hd) -> (b, sq, h, hd)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, hd)
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

def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per (token, head) int8 symmetric quantisation along head_dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  *, layers: int | None = None) -> dict[str, Any]:
    """Cache pytree (ShapeDtypeStruct-compatible via jax.eval_shape)."""
    hkv, hd = cfg.kv_heads_eff, cfg.hd
    shape = (batch, max_len, hkv, hd)
    if layers is not None:
        shape = (layers, *shape)
    if cfg.kv_cache_dtype == "int8":
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.activation_dtype),
        "v": jnp.zeros(shape, cfg.activation_dtype),
    }


def kv_cache_axes(cfg: ModelConfig, *, layers: bool = True) -> dict[str, tuple]:
    """Logical axes of the cache (leading 'layers' when stacked)."""
    lead = ("layers",) if layers else ()
    if cfg.attn_sharding == "heads":
        ax = lead + ("kv_batch", None, "kv_heads_sharded", None)
    else:
        ax = lead + ("kv_batch", "kv_seq_sharded", None, None)
    out = {"k": ax, "v": ax}
    if cfg.kv_cache_dtype == "int8":
        out["k_scale"] = ax[:-1] + (None,)
        out["v_scale"] = ax[:-1] + (None,)
    return out


def _cache_write(cache: dict[str, jax.Array], k: jax.Array, v: jax.Array,
                 pos: jax.Array, quantized: bool) -> dict[str, jax.Array]:
    """Write one new (B, 1, Hkv, hd) k/v at index pos (ring handled upstream).

    ``pos`` may be a scalar (all rows at the same depth) or a (B,) vector —
    the continuous-batching case where every slot sits at its own position.
    """
    per_row = getattr(pos, "ndim", 0) == 1

    def put(buf: jax.Array, upd: jax.Array) -> jax.Array:
        if per_row:
            return jax.vmap(
                lambda c, u, p: lax.dynamic_update_slice_in_dim(c, u, p,
                                                                axis=0)
            )(buf, upd, pos)
        return lax.dynamic_update_slice_in_dim(buf, upd, pos, axis=1)

    if quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {
            "k": put(cache["k"], kq),
            "v": put(cache["v"], vq),
            "k_scale": put(cache["k_scale"], ks),
            "v_scale": put(cache["v_scale"], vs),
        }
    return {
        "k": put(cache["k"], k),
        "v": put(cache["v"], v),
    }


def _cache_read(cfg: ModelConfig, cache: dict[str, jax.Array]):
    if cfg.kv_cache_dtype == "int8":
        k = dequantize_kv(cache["k"], cache["k_scale"], cfg.activation_dtype)
        v = dequantize_kv(cache["v"], cache["v_scale"], cfg.activation_dtype)
        return k, v
    return cache["k"], cache["v"]


def attn_decode(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                cache: dict[str, jax.Array], pos: jax.Array, *,
                window: int = 0) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 current position,
    or a (B,) int32 vector of *per-row* positions (continuous batching —
    each slot writes its k/v at, and attends up to, its own depth).

    For ``window > 0`` the cache is a ring buffer of length ``window`` —
    entries are written at ``pos % window`` and masked by recency. Ring
    buffers require a scalar ``pos`` (all rows advance in lockstep).
    """
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

    max_len = cache["k"].shape[1]
    write_pos = (pos % window) if window > 0 else pos
    cache = _cache_write(cache, k, v, write_pos, cfg.kv_cache_dtype == "int8")
    ck, cv = _cache_read(cfg, cache)

    # decode activations follow the CACHE's batch sharding (kv_batch): in
    # serve2d mode the residual stream is replicated but attention must run
    # batch-sharded against the sharded cache (GSPMD otherwise gathers it).
    if cfg.attn_sharding == "heads":
        ck = shard(ck, "kv_batch", None, "kv_heads_sharded", None)
        cv = shard(cv, "kv_batch", None, "kv_heads_sharded", None)
        q = shard(q, "kv_batch", None, "heads_sharded", None)
    else:
        ck = shard(ck, "kv_batch", "kv_seq_sharded", None, None)
        cv = shard(cv, "kv_batch", "kv_seq_sharded", None, None)
        q = shard(q, "kv_batch", None, None, None)

    hkv = ck.shape[2]
    h = q.shape[2]
    g = h // hkv
    hd = q.shape[-1]
    scale = cfg.attention_multiplier or (1.0 / float(hd) ** 0.5)

    # Flash-decode Pallas kernel path: ragged per-row lengths land directly
    # on the kernel's scalar-prefetch lens argument. Ring buffers and
    # soft-capping stay on the masked-einsum path below.
    if cfg.decode_impl == "pallas" and window == 0 and not cfg.attn_softcap:
        kv_len = (pos if per_row else jnp.broadcast_to(pos, (b,))) + 1
        out = _decode_kernel(cfg, q[:, 0], ck, cv, kv_len.astype(jnp.int32),
                             float(scale))
        out = out[:, None]                                  # (B, 1, H, hd)
        out = shard(out, "kv_batch", None, "heads_sharded", None)
        dt = x.dtype
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache

    # slot -> absolute position (ring buffers wrap)
    slots = jnp.arange(max_len, dtype=jnp.int32)
    if window > 0:
        cycle = (pos // window) * window
        k_pos = jnp.where(slots <= (pos % window), cycle + slots,
                          cycle - window + slots)
        kv_len = None
        valid = (k_pos >= 0) & (k_pos > pos - window) & (k_pos <= pos)
    elif per_row:
        valid = slots[None, :] <= pos[:, None]              # (B, Smax)
    else:
        valid = slots <= pos

    qg = q.reshape(b, 1, hkv, g, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, ck).astype(jnp.float32) * scale
    logits = _softcap(logits, cfg.attn_softcap)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    if per_row:
        logits = logits + bias[:, None, None, None, :]
    else:
        logits = logits + bias[None, None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, cv).reshape(b, 1, h, hd)
    out = shard(out, "kv_batch", None, "heads_sharded", None)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache


def _decode_kernel(cfg: ModelConfig, q, ck, cv, kv_len, scale: float):
    """The flash-decode kernel; under a mesh, run per shard via shard_map.

    A Pallas kernel cannot be partitioned automatically, so with axis
    rules active it runs on each device's block of the cache: batch rows
    over ``kv_batch``, KV heads (and their query-head groups) over
    ``kv_heads_sharded``. A cache sharded along the sequence needs a
    cross-shard softmax merge that does not exist yet; that case keeps the
    bare call, which the TPU compiler refuses (the CPU interpreter lowers
    it to plain HLO, which GSPMD partitions).
    """
    from repro.kernels.decode_attention import ops as da_ops

    call = functools.partial(da_ops.decode_attention, scale=scale,
                             block_kv=cfg.attn_kv_block)
    mesh = active_mesh()
    if mesh is None:
        return call(q, ck, cv, kv_len)
    cache_axes = kv_cache_axes(cfg, layers=False)["k"]
    batch, seq, heads, _ = logical_to_spec(cache_axes)
    seq_axes = (seq,) if isinstance(seq, str) else tuple(seq or ())
    if any(mesh.shape[a] > 1 for a in seq_axes):
        return call(q, ck, cv, kv_len)
    kv_spec = P(batch, None, heads, None)
    q_spec = P(batch, heads, None)
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(batch)),
                         out_specs=q_spec, check_vma=False)(q, ck, cv, kv_len)


def prefill_into_cache(cfg: ModelConfig, p: dict[str, jax.Array], x: jax.Array,
                       positions: jax.Array, cache: dict[str, jax.Array], *,
                       window: int = 0):
    """Prefill attention that also populates the cache for later decode."""
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        q, k = rope(q, k, positions, cfg.rope_theta)
    q, k, v = _shard_qkv(cfg, q, k, v)
    s = x.shape[1]
    quantized = cfg.kv_cache_dtype == "int8"
    if window > 0:
        # keep the last `window` entries in ring order
        w = min(window, s)
        ks, vs = k[:, s - w:], v[:, s - w:]
        start = (s - w) % window if window else 0
        # ring layout: slot (pos % window); since we write a contiguous tail,
        # roll so that slot indices line up.
        idx = (jnp.arange(w) + (s - w)) % window
        order = jnp.argsort(idx)
        ks, vs = ks[:, order], vs[:, order]
        if quantized:
            kq, ksc = quantize_kv(ks)
            vq, vsc = quantize_kv(vs)
            cache = dict(cache)
            cache["k"] = lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, 1)
            cache["v"] = lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, 1)
            cache["k_scale"] = lax.dynamic_update_slice_in_dim(cache["k_scale"], ksc, 0, 1)
            cache["v_scale"] = lax.dynamic_update_slice_in_dim(cache["v_scale"], vsc, 0, 1)
        else:
            cache = dict(cache)
            cache["k"] = lax.dynamic_update_slice_in_dim(cache["k"], ks, 0, 1)
            cache["v"] = lax.dynamic_update_slice_in_dim(cache["v"], vs, 0, 1)
    else:
        if quantized:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            cache = {
                "k": lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, 1),
                "v": lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, 1),
                "k_scale": lax.dynamic_update_slice_in_dim(cache["k_scale"], ksc, 0, 1),
                "v_scale": lax.dynamic_update_slice_in_dim(cache["v_scale"], vsc, 0, 1),
            }
        else:
            cache = {
                "k": lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                "v": lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
            }
    impl = _IMPLS[cfg.attn_impl]
    out = impl(cfg, q, k, v, positions[0] if positions.ndim > 1 else positions,
               positions[0] if positions.ndim > 1 else positions,
               causal=True, window=window)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache
