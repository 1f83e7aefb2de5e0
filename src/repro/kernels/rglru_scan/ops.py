"""Jit'd wrapper for the RG-LRU scan kernel with a custom VJP.

The linear recurrence has a closed-form adjoint which is itself a linear
recurrence run backwards:
    dL/dx_t = g_t,  where  g_t = dL/dh_t + a_{t+1} * g_{t+1}
    dL/da_t = g_t * h_{t-1}
    dL/dh0  = a_1 * g_1
so the same kernel (time-reversed) computes the backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.rglru_scan import kernel as K


def _scan(a, x, h0, block_t, block_d, interpret):
    return K.rglru_scan_kernel(a, x, h0, block_t=block_t, block_d=block_d,
                               interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rglru(a, x, h0, block_t, block_d, interpret):
    hs, h_last = _scan(a, x, h0, block_t, block_d, interpret)
    return hs, h_last


def _fwd(a, x, h0, block_t, block_d, interpret):
    hs, h_last = _scan(a, x, h0, block_t, block_d, interpret)
    return (hs, h_last), (a, hs, h0)


def _bwd(block_t, block_d, interpret, res, grads):
    a, hs, h0 = res
    dhs, dh_last = grads
    b, s, d = a.shape
    # incorporate the gradient wrt the final state into the last step
    dhs = dhs.astype(jnp.float32).at[:, -1, :].add(dh_last.astype(jnp.float32))
    # reverse-time recurrence: g_t = dhs_t + a_{t+1} g_{t+1}
    a_rev = jnp.flip(jnp.concatenate(
        [a.astype(jnp.float32)[:, 1:, :], jnp.zeros((b, 1, d), jnp.float32)],
        axis=1), axis=1)
    g_rev, _ = _scan(a_rev, jnp.flip(dhs, axis=1),
                     jnp.zeros((b, d), jnp.float32), block_t, block_d,
                     interpret)
    g = jnp.flip(g_rev, axis=1)
    h_prev = jnp.concatenate(
        [h0.astype(jnp.float32)[:, None, :], hs[:, :-1, :]], axis=1)
    da = g * h_prev
    dx = g
    dh0 = a.astype(jnp.float32)[:, 0, :] * g[:, 0, :]
    return da.astype(a.dtype), dx.astype(a.dtype), dh0.astype(h0.dtype)


_rglru.defvjp(_fwd, _bwd)


def rglru_scan(a, x, h0, *, block_t: int = 128, block_d: int = 512,
               interpret: bool | None = None):
    """h_t = a_t*h_{t-1} + x_t, blocked for TPU. Returns (hs, h_last)."""
    if interpret is None:
        interpret = interpret_default()
    return _rglru(a, x, h0, int(block_t), int(block_d), bool(interpret))
