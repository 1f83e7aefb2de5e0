"""Gated delta-rule (KDA) decode kernel: one token's state update and
readout for every slot and head, in place in the stacked state.

Each head's state is kept transposed, ``M = S^T`` (d_v, d_k): the keys'
channels on the lanes. The token's q, k and decay are then lane-dense
rows, v and the output columns of d_v, and the step is a few vector
operations a head with no transpose and no matrix product:

    M = M * exp(g)                      (d_v, d_k)   decay each key channel
    u = beta (v - sum_k M * k)          (d_v, 1)     the delta rule's error
    M = M + u * k                       (d_v, d_k)   column times row
    o = sum_k M * q                     (d_v, 1)

The grid is (slot, block of heads); the layer of the stacked state
``(L, B, H, d_v, d_k)`` is a scalar-prefetched index the state's index
map reads, and the state is written back into the same buffer
(``input_output_aliases``): blocks of other layers are never read or
written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, q_ref, k_ref, g_ref, v_ref, b_ref, s_ref, o_ref,
            so_ref):
    del layer_ref                                   # read by the index maps
    k = k_ref[0]                                    # (hb, 1, d_k)
    m = s_ref[0, 0] * jnp.exp(g_ref[0])             # (hb, d_v, d_k)
    u = b_ref[0] * (v_ref[0] - jnp.sum(m * k, axis=-1, keepdims=True))
    m = m + u * k
    so_ref[0, 0] = m
    o_ref[0] = jnp.sum(m * q_ref[0], axis=-1, keepdims=True)


def kda_decode_kernel(q, k, g, v, beta, state, layer, *, heads_block,
                      interpret):
    """q, k, g: (B, H, 1, d_k) float32 rows; v: (B, H, d_v, 1) float32
    columns; beta: (B, H, 1, 1); state: (L, B, H, d_v, d_k) float32,
    updated in place at ``layer``. Returns (o (B, H, d_v, 1), the
    state)."""
    nb, h, dv, _ = v.shape
    dk = q.shape[-1]
    hb = min(heads_block, h)
    while h % hb:
        hb //= 2

    def blk(*tail):
        return pl.BlockSpec((1, hb, *tail), lambda ib, ih, at: (ib, ih, 0, 0))

    row, col = blk(1, dk), blk(dv, 1)
    st = pl.BlockSpec((1, 1, hb, dv, dk),
                      lambda ib, ih, at: (at[0], ib, ih, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, h // hb),
        in_specs=[row, row, row, col, blk(1, 1), st],
        out_specs=[col, st],
    )
    at = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 counts the scalar prefetch: the state, into output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_decode",
    )(at, q, k, g, v, beta, state)
