"""Flash attention Pallas TPU kernels (fwd + bwd).

TPU adaptation of the GPU flash-attention algorithm: instead of warp-level
tiles, blocks are sized for VMEM and the MXU's 128-lane systolic array.
The KV axis is the innermost *sequential* grid dimension, so the online
softmax state (m, l, acc) lives in VMEM scratch that persists across KV
steps of one (batch, head, q-block) program — the TPU analogue of a GPU
thread-block's shared-memory accumulator.

Grid (fwd): (B, H, nQ, nKV); K/V index_map folds GQA: kv_head = h // G.
Fully-masked KV blocks are skipped with pl.when (causal/local windows).
Per-row statistics (m, l, the saved log-sum-exp and the backward's delta)
are (rows, 1) columns with a trailing unit axis: the TPU compiler accepts
a block whose last two dims are (multiple of 8, full dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _apply_softcap(logits, softcap):
    if softcap and softcap > 0.0:
        return softcap * jnp.tanh(logits / softcap)
    return logits


def _mask(bq, bkv, iq, ik, *, causal, window, q_offset):
    q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + q_offset
    k_pos = ik * bkv + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    return ok


def _block_needed(bq, bkv, iq, ik, *, causal, window, q_offset):
    """Static-shape test: could any element of this (iq, ik) tile be live?"""
    need = jnp.bool_(True)
    if causal:
        # first k of block must be <= last q of block
        need &= (ik * bkv) <= (iq * bq + bq - 1 + q_offset)
    if window > 0:
        # last k of block must be > first q - window
        need &= (ik * bkv + bkv - 1) > (iq * bq + q_offset - window)
    return need


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, softcap, q_offset, nkv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    needed = _block_needed(bq, bkv, iq, ik, causal=causal, window=window,
                           q_offset=q_offset)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (bq, bkv)
        logits = _apply_softcap(logits, softcap)
        ok = _mask(bq, bkv, iq, ik, causal=causal, window=window,
                   q_offset=q_offset)
        logits = jnp.where(ok, logits, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)))


def flash_attention_fwd(q, k, v, *, causal, window, scale, softcap, q_offset,
                        block_q, block_kv, interpret):
    """q: (B, Sq, H, hd); k/v: (B, Skv, Hkv, hd) -> (out, lse).

    lse is (B, H, Sq, 1)."""
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bkv = min(block_kv, skv)
    while skv % bkv:
        bkv //= 2
    nq, nkv = sq // bq, skv // bkv

    # layout: (B, H, S, hd) for q; (B, Hkv, S, hd) for k/v
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, nkv=nkv)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, window, softcap, q_offset, nkv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    needed = _block_needed(bq, bkv, iq, ik, causal=causal, window=window,
                           q_offset=q_offset)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if softcap and softcap > 0.0:
            t = jnp.tanh(raw / softcap)
            logits = softcap * t
            dcap = 1.0 - t * t
        else:
            logits = raw
            dcap = None
        ok = _mask(bq, bkv, iq, ik, causal=causal, window=window,
                   q_offset=q_offset)
        logits = jnp.where(ok, logits, NEG_INF)
        p = jnp.exp(logits - lse_ref[0, 0])
        do = do_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        if dcap is not None:
            ds = ds * dcap
        ds = jnp.where(ok, ds, 0.0)
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nkv - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                    softcap, q_offset, nq, group):
    ih = pl.program_id(1)
    ik = pl.program_id(2)
    ig = pl.program_id(3)   # inner loop over (q heads in group) x q blocks
    iq = ig % nq
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]
    del ih

    @pl.when(ig == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = _block_needed(bq, bkv, iq, ik, causal=causal, window=window,
                           q_offset=q_offset)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if softcap and softcap > 0.0:
            t = jnp.tanh(raw / softcap)
            logits = softcap * t
            dcap = 1.0 - t * t
        else:
            logits = raw
            dcap = None
        ok = _mask(bq, bkv, iq, ik, causal=causal, window=window,
                   q_offset=q_offset)
        logits = jnp.where(ok, logits, NEG_INF)
        p = jnp.exp(logits - lse_ref[0, 0])                  # (bq, bkv)
        do = do_ref[0, 0].astype(jnp.float32)                # (bq, hd)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bkv, hd)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        if dcap is not None:
            ds = ds * dcap
        ds = jnp.where(ok, ds, 0.0)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bkv, hd)

    total = nq * group

    @pl.when(ig == total - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal, window, scale,
                        softcap, q_offset, block_q, block_kv, interpret):
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bkv = min(block_kv, skv)
    while skv % bkv:
        bkv //= 2
    nq, nkv = sq // bq, skv // bkv

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    # delta = rowsum(do * out) per (b, h, s), laid out like lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[..., None]   # (B, H, Sq, 1)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, nkv=nkv)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # dk/dv: grid over kv blocks; inner dim walks (group*nq) q tiles
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, nq=nq, group=g)

    def qmap(ib, ih, ik, ig, g=g, nq=nq):
        return (ib, ih * g + ig // nq, ig % nq, 0)

    def lmap(ib, ih, ik, ig, g=g, nq=nq):
        return (ib, ih * g + ig // nq, ig % nq, 0)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, nkv, g * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), qmap),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, ik, ig: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, ik, ig: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bq, hd), qmap),
            pl.BlockSpec((1, 1, bq, 1), lmap),
            pl.BlockSpec((1, 1, bq, 1), lmap),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, ik, ig: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda ib, ih, ik, ig: (ib, ih, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, hd), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, hd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bkv, hd), jnp.float32),
                        pltpu.VMEM((bkv, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))
