"""Mamba-2 decode kernel: one token's state update and readout for every
slot and head, in place in the stacked state.

The state of one slot is kept ``(N, H*P)``: d_state rows, and the heads'
channels side by side along the lanes (``state[n, h*P + p]`` is the
published ``S[h, p, n]``). Every operand is then a lane-dense row or a
column of N, and the step is three vector operations a tile with no
transpose:

    dA = exp(dt * A)                      (1, blk)   each head's decay
    S  = dA * S + B (dt * x)              (N, blk)   column times row
    y  = sum_n C S + D * x                (1, blk)

where ``dt``, ``A`` and ``D`` come repeated over each head's P channels,
and B and C as columns of their head group. The grid is (slot, block of
channels); the layer of the stacked state ``(L, B, N, H*P)`` is a
scalar-prefetched index the state's index map reads, and the state is
written back into the same buffer (``input_output_aliases``): blocks of
other layers are never read or written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref,
            y_ref, so_ref):
    del layer_ref                                   # read by the index maps
    x = x_ref[0]                                    # (1, blk)
    dt = dt_ref[0]
    decay = jnp.exp(dt * a_ref[...])
    s = s_ref[0, 0] * decay + b_ref[0, 0] * (dt * x)    # (N, blk)
    so_ref[0, 0] = s
    y_ref[0] = jnp.sum(s * c_ref[0, 0], axis=0, keepdims=True) + \
        d_ref[...] * x


def ssm_decode_kernel(x, dt, a, d, b, c, state, layer, *, block, interpret):
    """x, dt: (B, 1, H*P) float32 (dt repeated over each head's P); a, d:
    (1, H*P); b, c: (B, G, N, 1) float32 columns; state: (L, B, N, H*P)
    float32, updated in place at ``layer``. Returns (y (B, 1, H*P), the
    state)."""
    nb, _, width = x.shape
    groups, n = b.shape[1], b.shape[2]
    span = width // groups                          # one group's channels
    blk = min(block, span)
    while span % blk:
        blk //= 2
    per_group = span // blk

    row = pl.BlockSpec((1, 1, blk), lambda ib, ij, at: (ib, 0, ij))
    lanes = pl.BlockSpec((1, blk), lambda ib, ij, at: (0, ij))
    col = pl.BlockSpec((1, 1, n, 1),
                       lambda ib, ij, at: (ib, ij // per_group, 0, 0))
    st = pl.BlockSpec((1, 1, n, blk), lambda ib, ij, at: (at[0], ib, 0, ij))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, width // blk),
        in_specs=[row, row, lanes, lanes, col, col, st],
        out_specs=[row, st],
    )
    at = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 counts the scalar prefetch: the state, into output 1
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_decode",
    )(at, x, dt, a, d, b, c, state)
