"""Flash-decode Pallas kernel: one query token against a deep KV cache.

Decode is HBM-bandwidth bound (the live cache is read once per token); the
kernel streams KV blocks through VMEM with the online-softmax recurrence,
grid = (B, nKV) with the KV axis innermost/sequential. A block holds every
KV head of its row, (Hkv, hd, bkv), and all G query heads of each KV head
are scored in one matmul batched over heads, so the cache is read ONCE per
group (the GQA arithmetic-intensity win) in a few large copies. Per-row
cache lengths arrive via scalar prefetch (SMEM), letting one batch mix
ragged sequence lengths; a block past a row's length maps to its last
needed block (``needed_block``), so it is neither fetched nor computed.
The block's positions come from the shape (``kv_block``): ``block_kv``,
halved while a double-buffered K and V pair passes ``KV_PAIR_BYTES``.

Both kernels take the model's whole stacked cache, positions last:
(L, B, Hkv, hd, Smax) or (L, B, C, Smax), and the index of the layer to
read, a second scalar prefetch that the k/v index maps use. Each layer's
blocks are read straight from the stack in HBM, so the decode step passes
the buffer it updates in place and nothing slices a layer out for the
call. With positions last, the TPU's default layout of the buffer is the
row-major one the kernel's call needs (a last axis of hd = 64 or
C = 576 makes XLA lay positions out minor instead, and the call would
then need a relayout of the whole cache).

``latent_decode_attention`` is the same recurrence against a latent cache
(MLA's absorbed decode): one latent row of C per position, shared by
every head, each read once for all heads, scored over all C and summed as
values over its first ``value_dim``, with the same clamp of its blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def needed_block(ik, kv_len, bkv):
    """The block a row reads at grid step ``ik``: ``ik`` itself up to the
    row's last needed block, that block past it (block 0 for an empty
    row). A step whose index repeats the last one fetches nothing."""
    last = jnp.maximum((kv_len + bkv - 1) // bkv - 1, 0)
    return jnp.minimum(ik, last)


def kv_block_map(ib, ik, at, lens, *, bkv):
    """The k/v index map: layer ``at[0]``, row ``ib``, every KV head, the
    row's needed block at grid step ``ik``."""
    return at[0], ib, 0, 0, needed_block(ik, lens[ib], bkv)


#: fast-memory budget of one double-buffered K and V block pair
KV_PAIR_BYTES = 8 * 2**20


def kv_block(hkv, hd, smax, block_kv, itemsize):
    """Positions of one K or V block: ``block_kv`` cut to a divisor of
    ``smax``, then halved while a double-buffered pair of blocks, every
    KV head of a row, passes ``KV_PAIR_BYTES``."""
    bkv = min(block_kv, smax)
    while smax % bkv:
        bkv //= 2
    while bkv > 128 and 4 * hkv * hd * bkv * itemsize > KV_PAIR_BYTES:
        bkv //= 2
    return bkv


def _kernel(layer_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, nkv, bkv):
    del layer_ref                                         # read by the index maps
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = lens_ref[ib]
    needed = (ik * bkv) < kv_len

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (Hkv, G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (Hkv, hd, bkv)
        logits = lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        pos = ik * bkv + lax.broadcasted_iota(jnp.int32, (1, 1, bkv), 2)
        logits = jnp.where(pos < kv_len, logits, NEG_INF)  # (Hkv, G, bkv)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=2))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2)
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                             (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
        m_scr[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def _prefetch(layer, kv_len, b):
    """The scalar-prefetch operands: the layer index as (1,), the
    lengths as (B,), both int32."""
    return (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
            jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,)))


def decode_attention_kernel(q, k, v, kv_len, layer, *, scale, block_kv,
                            interpret):
    """q: (B, H, hd); k/v: (L, B, Hkv, hd, Smax) stacked; kv_len: (B,)
    int32; layer: the index of the layer to read."""
    b, h, hd = q.shape
    hkv, smax = k.shape[2], k.shape[4]
    g = h // hkv
    bkv = kv_block(hkv, hd, smax, block_kv, k.dtype.itemsize)
    nkv = smax // bkv

    qg = q.reshape(b, hkv, g, hd)
    rows = functools.partial(kv_block_map, bkv=bkv)

    kernel = functools.partial(_kernel, scale=scale, nkv=nkv, bkv=bkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv),
        in_specs=[
            # index maps receive the scalar-prefetch refs as trailing args
            pl.BlockSpec((1, hkv, g, hd),
                         lambda ib, ik, at, lens: (ib, 0, 0, 0)),
            pl.BlockSpec((1, 1, hkv, hd, bkv), rows),
            pl.BlockSpec((1, 1, hkv, hd, bkv), rows),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, hd),
                               lambda ib, ik, at, lens: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(*_prefetch(layer, kv_len, b), qg, k, v)
    return out.reshape(b, h, hd)


def _latent_kernel(layer_ref, lens_ref, q_ref, c_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale, nkv, bkv, dv):
    del layer_ref                                         # read by the index map
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = lens_ref[ib]

    @pl.when((ik * bkv) < kv_len)
    def _compute():
        rows = c_ref[0, 0]                                # (C, bkv)
        logits = lax.dot_general(q_ref[0], rows, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        pos = ik * bkv + lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        logits = jnp.where(pos < kv_len, logits, NEG_INF)  # (H, bkv)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        pv = lax.dot_general(p.astype(rows.dtype), rows[:dv],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def latent_decode_attention_kernel(q, cache, kv_len, layer, *, scale,
                                   value_dim, block_kv, interpret):
    """q: (B, H, C); cache: (L, B, C, Smax) stacked; kv_len: (B,) int32;
    layer: the index of the layer to read. Returns (B, H, value_dim)."""
    b, h, c = q.shape
    smax = cache.shape[3]
    bkv = min(block_kv, smax)
    while smax % bkv:
        bkv //= 2
    nkv = smax // bkv

    def rows(ib, ik, at, lens):
        return at[0], ib, 0, needed_block(ik, lens[ib], bkv)

    kernel = functools.partial(_latent_kernel, scale=scale, nkv=nkv, bkv=bkv,
                               dv=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv),
        in_specs=[
            pl.BlockSpec((1, h, c), lambda ib, ik, at, lens: (ib, 0, 0)),
            pl.BlockSpec((1, 1, c, bkv), rows),
        ],
        out_specs=pl.BlockSpec((1, h, value_dim),
                               lambda ib, ik, at, lens: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(*_prefetch(layer, kv_len, b), q, cache)
