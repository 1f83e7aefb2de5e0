"""Seeded weights: benchmark data, not the program's.

The configuration's architecture module (``bench/refs/<reference>.py``)
declares its leaves in ``leaf_specs``; this module draws and writes them
whatever the architecture. Every leaf is drawn from ``(seed, leaf name,
layer)`` alone, so the reference can draw one layer at a time and the
program's copy is written leaf by leaf into the buffers it already holds
(no second copy of the weights ever exists on the device).
"""

from __future__ import annotations

import functools
import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    """One weight leaf as an architecture module declares it."""

    #: shape of one layer's slice (of the whole leaf where ``depth`` is 0)
    shape: tuple[int, ...]
    std: float
    mean: float
    #: layers the program stacks the leaf over on its axis 0; 0 for a
    #: leaf drawn once
    depth: int = 0
    #: the axis that runs over the vocabulary: the program pads it, and
    #: the padding is written as zeros (leaves drawn once only)
    vocab_axis: int | None = None


def _base_key(seed: int) -> jax.Array:
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _draw(key, layer, shape, std, mean):
    k = jax.random.fold_in(key, layer)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def draw(specs: dict[str, Leaf], seed: int, name: str,
         layer: int = 0) -> jax.Array:
    """One leaf (one layer of a stacked leaf) in float32."""
    shape, std, mean = specs[name][:3]
    key = jax.random.fold_in(_base_key(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _draw(key, jnp.int32(layer), shape, float(std), float(mean))


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_layer(buf, layer, value):
    return jax.lax.dynamic_update_index_in_dim(buf, value.astype(buf.dtype),
                                               layer, 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(buf, value):
    # the program's padded vocabulary beyond the real one is zero
    out = jnp.zeros_like(buf)
    return jax.lax.dynamic_update_slice(out, value.astype(buf.dtype),
                                        (0,) * buf.ndim)


def _holds(have: tuple[int, ...], leaf: Leaf) -> bool:
    """A buffer drawn once holds the leaf: the same shape, the
    vocabulary axis at least as long."""
    return len(have) == len(leaf.shape) and all(
        h == w or (i == leaf.vocab_axis and h > w)
        for i, (h, w) in enumerate(zip(have, leaf.shape)))


def path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def overwrite(params, specs: dict[str, Leaf], seed: int):
    """Write the seeded weights into ``params`` leaf by leaf, donating the
    program's buffers; returns the new tree. Raises when the program's
    tree does not hold exactly the declared leaves at the declared shapes
    and depths."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [path_name(p) for p, _ in leaves]
    if sorted(names) != sorted(specs):
        raise ValueError(f"program parameters {sorted(names)} are not the "
                         f"reference's {sorted(specs)}")
    out = []
    for name, (_, buf) in zip(names, leaves):
        leaf = specs[name]
        if leaf.depth:
            if buf.shape != (leaf.depth, *leaf.shape):
                raise ValueError(f"{name}: program shape {buf.shape}, "
                                 f"declared {leaf.depth} x {leaf.shape}")
            for layer in range(leaf.depth):
                buf = _put_layer(buf, layer, draw(specs, seed, name, layer))
        else:
            if not _holds(buf.shape, leaf):
                raise ValueError(f"{name}: program shape {buf.shape}, "
                                 f"declared {leaf.shape}")
            buf = _put(buf, draw(specs, seed, name))
        out.append(buf)
    return jax.tree_util.tree_unflatten(treedef, out)
