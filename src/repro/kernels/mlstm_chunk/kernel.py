"""Chunkwise-parallel mLSTM Pallas kernel (xLSTM matrix memory).

Grid (B, H, nChunks) with the chunk axis innermost/sequential; the
inter-chunk state (C: hd×hd matrix memory, n: hd normalizer, m: scalar
stabiliser) persists in VMEM scratch. Intra-chunk work is two MXU matmuls
(qk^T and the dv-style combine) over an L×L decay-masked score matrix —
the TPU-native replacement for the paper's fused CUDA recurrence.

Gate vectors enter both as columns (L, 1) and as rows (1, L) — the
wrapper lays them out, so the kernel needs no in-register transpose —
and the within-chunk cumulative forget gate is precomputed the same way.
Vector and scalar state carry a unit axis ((1, hd) and (1, 1)): every
block's last two dims are then multiples of (8, 128) or full dims, which
the TPU compiler requires.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1.0e30


def _kernel(q_ref, k_ref, v_ref, bc_ref, br_ref, lic_ref, lir_ref, c0_ref,
            n0_ref, m0_ref, h_ref, cf_ref, nf_ref, mf_ref, C_scr, n_scr,
            m_scr, *, nc, L, scale):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        C_scr[...] = c0_ref[0, 0]
        n_scr[...] = n0_ref[0, 0]
        m_scr[...] = jnp.maximum(m0_ref[0, 0], NEG_BIG)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (L, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    b_col = bc_ref[0, 0]                              # (L, 1) inclusive cumsum
    b_row = br_ref[0, 0]                              # (1, L)
    li_col = lic_ref[0, 0]                            # (L, 1)
    li_row = lir_ref[0, 0]                            # (1, L)

    total = b_row[:, L - 1:]                          # (1, 1)
    m_prev = m_scr[...]                               # (1, 1)
    C_prev = C_scr[...]                               # (hd, hd)
    n_prev = n_scr[...]                               # (1, hd)

    # intra-chunk decay matrix D[t, s] = b_t - b_s + li_s for s <= t
    tri = lax.broadcasted_iota(jnp.int32, (L, L), 1) <= \
        lax.broadcasted_iota(jnp.int32, (L, L), 0)
    D = jnp.where(tri, b_col - b_row + li_row, NEG_BIG)
    m_intra = jnp.max(D, axis=1, keepdims=True)       # (L, 1)
    m_inter = b_col + m_prev
    m_out = jnp.maximum(jnp.maximum(m_intra, m_inter), NEG_BIG)

    inter_scale = jnp.exp(m_inter - m_out)            # (L, 1)
    h_inter = lax.dot_general(q, C_prev, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    den_inter = jnp.sum(q * n_prev, axis=1, keepdims=True)

    P = jnp.exp(D - m_out)
    att = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32) * P
    h_intra = lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    den_intra = jnp.sum(att, axis=1, keepdims=True)
    num = h_inter * inter_scale + h_intra
    den = den_inter * inter_scale + den_intra
    denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_out))
    h_ref[0, 0] = (num / denom).astype(h_ref.dtype)

    # inter-chunk state update with per-chunk stabiliser
    m_cand = jnp.max(li_col + total - b_col, axis=0, keepdims=True)
    m_new = jnp.maximum(m_prev + total, m_cand)       # (1, 1)
    c_scale = jnp.exp(m_prev + total - m_new)
    ks = k * jnp.exp(li_col + total - b_col - m_new)  # (L, hd)
    kv = lax.dot_general(ks, v, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)   # (hd, hd)
    C_scr[...] = C_prev * c_scale + kv
    n_scr[...] = n_prev * c_scale + jnp.sum(ks, axis=0, keepdims=True)
    m_scr[...] = m_new

    @pl.when(ic == nc - 1)
    def _final():
        cf_ref[0, 0] = C_scr[...]
        nf_ref[0, 0] = n_scr[...]
        mf_ref[0, 0] = m_scr[...]


def mlstm_chunk_kernel(q, k, v, li, lf, C0, n0, m0, *, chunk, interpret):
    """q/k/v: (B, H, S, hd); li/lf: (B, H, S); carried state C0 (B,H,hd,hd),
    n0 (B,H,hd), m0 (B,H). Returns (h, (C, n, m)) with fp32 state."""
    b, h, s, hd = q.shape
    L = min(chunk, s)
    while s % L:
        L //= 2
    nc = s // L
    scale = 1.0 / float(hd) ** 0.5

    f32 = jnp.float32
    li = li.astype(f32)
    # cumulative forget gate, restarted at every chunk boundary
    b_cum = jnp.cumsum(lf.astype(f32).reshape(b, h, nc, L),
                       axis=-1).reshape(b, h, s)

    def col(x):
        return x[..., None]                          # (B, H, S, 1)

    def row(x):
        return x[:, :, None, :]                      # (B, H, 1, S)

    seq = pl.BlockSpec((1, 1, L, hd), lambda ib, ih, ic: (ib, ih, ic, 0))
    col_spec = pl.BlockSpec((1, 1, L, 1), lambda ib, ih, ic: (ib, ih, ic, 0))
    row_spec = pl.BlockSpec((1, 1, 1, L), lambda ib, ih, ic: (ib, ih, 0, ic))
    mat = pl.BlockSpec((1, 1, hd, hd), lambda ib, ih, ic: (ib, ih, 0, 0))
    vec = pl.BlockSpec((1, 1, 1, hd), lambda ib, ih, ic: (ib, ih, 0, 0))
    one = pl.BlockSpec((1, 1, 1, 1), lambda ib, ih, ic: (ib, ih, 0, 0))

    kernel = functools.partial(_kernel, nc=nc, L=L, scale=scale)
    hs, cf, nf, mf = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[seq, seq, seq, col_spec, row_spec, col_spec, row_spec,
                  mat, vec, one],
        out_specs=[seq, mat, vec, one],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), f32),
            jax.ShapeDtypeStruct((b, h, 1, hd), f32),
            jax.ShapeDtypeStruct((b, h, 1, 1), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hd, hd), f32),
            pltpu.VMEM((1, hd), f32),
            pltpu.VMEM((1, 1), f32),
        ],
        interpret=interpret,
    )(q, k, v, col(b_cum), row(b_cum), col(li), row(li), C0.astype(f32),
      n0.astype(f32)[:, :, None, :], m0.astype(f32)[:, :, None, None])
    return hs, (cf, nf[:, :, 0], mf[:, :, 0, 0])
