"""Grouped-matmul decode kernel for the held-expert layer: the routed
held experts of one layer, read in place from the run's stacked expert
matrices.

At decode the layer sees a few rows (one a slot), and each reaches few of
the experts this chip holds. The kernel reads only those: a scalar
prefetch carries the layer's index, the held experts that some row routes
to, compacted to the front of a list of all ``n`` held experts and padded
by repeating the last of them, and their count. The grid is (list entry
j, tile of the expert's width F), over the routed entries only (the count
is a dynamic grid bound, at least 1); the weight index maps name
``(layer, ids[j], tile)`` straight in the stacked ``(L, n, D, F)`` and
``(L, n, F, D)`` buffers. A padded entry names the block already in VMEM
(the last tile of the last routed expert), so the pipeline would not fetch
it again, and ``pl.when(j < count)`` skips its compute: with no expert
routed the one grid entry reads one block and the output stays zero.

Each routed expert takes all T rows through gate, up, the activation and
down in one pass, a tile of F at a time, and adds ``gate x expert(x)`` to
the float32 ``(T, D)`` output for the rows routed there, selected with
``jnp.where``. No sort, gather or scatter of rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: columns of F a grid step reads. Measured on one v5e at both expert
#: cells' shapes: tiles of 128 up to the whole width read at the same rate
TILE = 128


def weight_block(j, t, ids, count, *, tiles):
    """(expert, tile of F) that grid point ``(j, t)`` reads: a padded entry
    (``j >= count``) stays on the last tile of the expert it repeats."""
    return ids[j], jnp.where(j < count[0], t, tiles - 1)


def _kernel(layer_ref, ids_ref, count_ref, x_ref, gate_ref, routed_ref,
            wg_ref, wu_ref, wd_ref, o_ref, *, act):
    del layer_ref, ids_ref                        # read by the index maps
    j, t = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (t == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < count_ref[0])
    def _expert():
        x = x_ref[...]                                        # (T, D)
        g = jnp.dot(x, wg_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32)       # (T, tile)
        u = jnp.dot(x, wu_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        h = (act(g) * u).astype(x.dtype)
        y = jnp.dot(h, wd_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32)       # (T, D)
        routed = jnp.broadcast_to(routed_ref[0], y.shape) != 0
        o_ref[...] += jnp.where(routed, gate_ref[0] * y, 0.0)


def moe_decode_kernel(x, gate, routed, ids, count, w_gate, w_up, w_down,
                      layer, *, act, interpret):
    """x: (T, D); gate, routed: (n, T, 1), each row's float32 gate weight
    for each held expert and whether it routes there (int32); ids (n,) and
    count (1,): the schedule; w_gate, w_up: (L, n, D, F) and w_down: (L,
    n, F, D) stacked; layer: the index to read. Returns (T, D) float32."""
    rows, d = x.shape
    f = w_gate.shape[3]
    tile = min(TILE, f)
    while f % tile:
        tile //= 2
    tiles = f // tile
    block = functools.partial(weight_block, tiles=tiles)

    def up(j, t, at, ids, count):
        e, tt = block(j, t, ids, count)
        return at[0], e, 0, tt

    def down(j, t, at, ids, count):
        e, tt = block(j, t, ids, count)
        return at[0], e, tt, 0

    column = pl.BlockSpec((1, rows, 1),
                          lambda j, t, at, ids, count: (ids[j], 0, 0))
    whole = pl.BlockSpec((rows, d), lambda j, t, at, ids, count: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # only the routed experts' entries, and one when none is routed
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[whole, column, column,
                  pl.BlockSpec((1, 1, d, tile), up),
                  pl.BlockSpec((1, 1, d, tile), up),
                  pl.BlockSpec((1, 1, tile, d), down)],
        out_specs=whole,
    )
    at = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_decode",
    )(at, ids, count, x, gate, routed, w_gate, w_up, w_down)
