"""Jit'd wrapper for the held-expert decode kernel (forward only).

Turns the router's choices into what the kernel reads: each row's gate
weight and routed flag for each held expert, and the schedule of held
experts that some row routes to."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.moe_decode import kernel as K
from repro.models.common import act_fn


def schedule(routed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """routed (T, n) bool: which held experts each row routes to. Returns
    (ids (n,) int32, count (1,) int32): the held experts that some row
    routes to, in ascending order, padded by repeating the last of them
    (expert 0 when none is routed), and how many they are."""
    active = jnp.any(routed, axis=0)
    n = active.shape[0]
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32)
    ids = jnp.where(jnp.arange(n) < count, order,
                    order[jnp.maximum(count - 1, 0)])
    return ids, count[None]


def moe_decode(x, experts, weights, w_gate, w_up, w_down, layer, *,
               act: str = "silu", interpret: bool | None = None):
    """x (T, D); experts (T, k): each choice's index among the held experts
    (outside [0, n) for an expert held elsewhere); weights (T, k) float32
    gate weights; w_gate, w_up (L, n, D, F) and w_down (L, n, F, D) the
    run's stacks, read at ``layer``. Returns ((T, D) float32, the number of
    held experts read, int32)."""
    if interpret is None:
        interpret = interpret_default()
    n = w_gate.shape[1]
    hit = experts[:, :, None] == jnp.arange(n)               # (T, k, n)
    routed = jnp.any(hit, axis=1)                            # (T, n)
    gate = jnp.sum(jnp.where(hit, weights[..., None].astype(jnp.float32),
                             0.0), axis=1)
    ids, count = schedule(routed)
    out = K.moe_decode_kernel(
        x, gate.T[..., None], routed.T[..., None].astype(jnp.int32), ids,
        count, w_gate, w_up, w_down, layer, act=act_fn(act),
        interpret=bool(interpret))
    return out, count[0]
