"""Oracle for the held-expert decode kernel: every held expert of the
layer, one at a time, over every row."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.models.common import act_fn


def moe_decode_ref(x, experts, weights, w_gate, w_up, w_down, layer, *,
                   act: str = "silu"):
    """The kernel's arguments (``ops.moe_decode``): x (T, D), experts and
    weights (T, k), the stacked (L, n, D, F) / (L, n, F, D) matrices and the
    layer to read. Returns (T, D) float32: the sum over held experts e and
    the rows routed to e of the row's gate weight times expert e of it."""
    f32 = jnp.float32
    out = jnp.zeros(x.shape, f32)
    for e in range(w_gate.shape[1]):
        wg, wu, wd = (lax.dynamic_index_in_dim(w, layer, keepdims=False)[e]
                      .astype(f32) for w in (w_gate, w_up, w_down))
        xf = x.astype(f32)
        h = act_fn(act)(jnp.dot(xf, wg, precision=lax.Precision.HIGHEST)) * \
            jnp.dot(xf, wu, precision=lax.Precision.HIGHEST)
        y = jnp.dot(h, wd, precision=lax.Precision.HIGHEST)
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        out = out + jnp.where(jnp.any(experts == e, axis=1)[:, None],
                              gate[:, None] * y, 0.0)
    return out
