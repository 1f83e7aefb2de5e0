"""Jit'd public wrapper for the flash attention kernel with custom VJP.

``interpret`` defaults to True off-TPU so the same call sites work in CPU
tests; on TPU the compiled kernel runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.flash_attention import kernel as K


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, window, scale, softcap, q_offset, block_q,
           block_kv, interpret):
    out, _ = K.flash_attention_fwd(
        q, k, v, causal=causal, window=window, scale=scale, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, window, scale, softcap, q_offset, block_q,
               block_kv, interpret):
    out, lse = K.flash_attention_fwd(
        q, k, v, causal=causal, window=window, scale=scale, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, scale, softcap, q_offset, block_q, block_kv,
               interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = K.flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, window=window, scale=scale,
        softcap=softcap, q_offset=q_offset, block_q=block_q,
        block_kv=block_kv, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, softcap: float = 0.0,
                    q_offset: int = 0, block_q: int = 512,
                    block_kv: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Skv, Hkv, hd). Returns (B, Sq, H, hd)."""
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = interpret_default()
    q_offset = int(q_offset) if not hasattr(q_offset, "shape") else 0
    return _flash(q, k, v, causal, window, float(scale), float(softcap),
                  q_offset, int(block_q), int(block_kv), bool(interpret))
