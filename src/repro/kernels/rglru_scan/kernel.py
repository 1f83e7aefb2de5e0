"""RG-LRU linear-recurrence Pallas kernel.

TPU adaptation of the GPU scan: no warp shuffles exist, so the recurrence
is blocked — grid (nD, nT) with the TIME axis innermost (sequential on
TPU); the carry h lives in VMEM scratch and persists across time blocks.
Inside a block the recurrence h_t = a_t*h_{t-1} + x_t is evaluated with a
log2(bt)-step Blelloch-style doubling on the VPU (dense (B, bt, dblk)
element-wise ops), which beats a bt-step serial loop on a vector unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, h0_ref, hs_ref, hlast_ref, h_scr, *, nt, bt):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)     # (B, bt, dblk)
    x = x_ref[...].astype(jnp.float32)

    # in-block parallel prefix: after k rounds, for each t,
    #   x[t] = combined update over (t-2^k, t];  a[t] = product of decays.
    # The shift by k along time is a rotation whose wrapped-around head
    # (t < k) is masked: x gains nothing there, a keeps its value.
    t = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    k = 1
    while k < bt:
        live = t >= k
        a_shift = pltpu.roll(a, k, 1)
        x_shift = pltpu.roll(x, k, 1)
        x = x + jnp.where(live, a * x_shift, 0.0)
        a = a * jnp.where(live, a_shift, 1.0)
        k *= 2

    hs = x + a * h_scr[...][:, None, :]
    hs_ref[...] = hs.astype(hs_ref.dtype)
    h_scr[...] = hs_ref[:, bt - 1, :]                   # static-index load

    @pl.when(it == nt - 1)
    def _final():
        hlast_ref[...] = h_scr[...]


def rglru_scan_kernel(a, x, h0, *, block_t, block_d, interpret):
    """a, x: (B, S, D); h0: (B, D) -> (hs (B,S,D) fp32, h_last (B,D) fp32)."""
    b, s, d = a.shape
    bt = min(block_t, s)
    while s % bt:
        bt //= 2
    bd = min(block_d, d)
    while d % bd:
        bd //= 2
    nt, nd = s // bt, d // bd

    kernel = functools.partial(_kernel, nt=nt, bt=bt)
    hs, h_last = pl.pallas_call(
        kernel,
        grid=(nd, nt),
        in_specs=[
            pl.BlockSpec((b, bt, bd), lambda idd, it: (0, it, idd)),
            pl.BlockSpec((b, bt, bd), lambda idd, it: (0, it, idd)),
            pl.BlockSpec((b, bd), lambda idd, it: (0, idd)),
        ],
        out_specs=[
            pl.BlockSpec((b, bt, bd), lambda idd, it: (0, it, idd)),
            pl.BlockSpec((b, bd), lambda idd, it: (0, idd)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, bd), jnp.float32)],
        interpret=interpret,
    )(a, x, h0)
    return hs, h_last
