"""Seeded weights of a dense decoder: benchmark data, not the program's.

Every leaf is drawn from ``(seed, leaf name, layer)`` alone, so the
reference can draw one layer at a time and the program's copy is written
leaf by leaf into the buffers it already holds (no second copy of the
weights ever exists on the device). Matrices take the standard deviation
of their true fan-in, the product of the dimensions they sum over;
norm scales sit near 1 and biases near 0, both non-trivial so that a
dropped scale or bias shows.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

def leaf_specs(m: dict) -> dict[str, tuple[tuple[int, ...], float, float]]:
    """name -> (per-layer shape, standard deviation, mean); ``layers.*``
    are stacked over the depth, the rest are drawn once."""
    d, h, kv, hd, f = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    # q and k are scaled so that attention logits have unit variance under
    # the configured attention multiplier (Granite's 1/64 would otherwise
    # leave attention uniform and the continuation a function of the last
    # token alone)
    scale = m.get("attention_multiplier") or hd ** -0.5
    qk = (math.sqrt(hd) * scale) ** -0.5 / math.sqrt(d)
    specs = {
        # the embedded input (times the embedding multiplier) has unit norm
        # per row; a larger one makes tied logits favour the input token
        # so strongly that greedy decoding repeats it forever
        "embedding": ((m["vocab_size"], d),
                      1 / (m.get("embedding_multiplier", 1.0) * math.sqrt(d)),
                      0.0),
        "ln_final": ((d,), 0.05, 1.0),
        "layers.ln_attn": ((d,), 0.05, 1.0),
        "layers.ln_mlp": ((d,), 0.05, 1.0),
        "layers.attn.wq": ((d, h, hd), qk, 0.0),
        "layers.attn.wk": ((d, kv, hd), qk, 0.0),
        "layers.attn.wv": ((d, kv, hd), 1 / math.sqrt(d), 0.0),
        "layers.attn.wo": ((h, hd, d), 1 / math.sqrt(h * hd), 0.0),
        "layers.mlp.w_gate": ((d, f), 1 / math.sqrt(d), 0.0),
        "layers.mlp.w_up": ((d, f), 1 / math.sqrt(d), 0.0),
        "layers.mlp.w_down": ((f, d), 1 / math.sqrt(f), 0.0),
    }
    if m.get("qkv_bias"):
        specs["layers.attn.bq"] = ((h, hd), 0.1, 0.0)
        specs["layers.attn.bk"] = ((kv, hd), 0.1, 0.0)
        specs["layers.attn.bv"] = ((kv, hd), 0.1, 0.0)
    return specs


def _base_key(seed: int) -> jax.Array:
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _draw(key, layer, shape, std, mean):
    k = jax.random.fold_in(key, layer)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def draw(m: dict, seed: int, name: str, layer: int = 0) -> jax.Array:
    """One leaf (one layer of a stacked leaf) in float32."""
    shape, std, mean = leaf_specs(m)[name]
    key = jax.random.fold_in(_base_key(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _draw(key, jnp.int32(layer), shape, float(std), float(mean))


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_layer(buf, layer, value):
    return jax.lax.dynamic_update_index_in_dim(buf, value.astype(buf.dtype),
                                               layer, 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_rows(buf, value):
    # padded vocabulary rows beyond the real ones are zero
    out = jnp.zeros_like(buf)
    return jax.lax.dynamic_update_slice_in_dim(out, value.astype(buf.dtype),
                                               0, 0)


def path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def overwrite(params, m: dict, seed: int):
    """Write the seeded weights into ``params`` leaf by leaf, donating the
    program's buffers; returns the new tree. Raises when the program's
    tree does not hold exactly the leaves the reference expects."""
    specs = leaf_specs(m)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [path_name(p) for p, _ in leaves]
    if sorted(names) != sorted(specs):
        raise ValueError(f"program parameters {sorted(names)} are not the "
                         f"reference's {sorted(specs)}")
    out = []
    for name, (_, buf) in zip(names, leaves):
        shape = specs[name][0]
        if name.startswith("layers."):
            if buf.shape != (m["num_hidden_layers"], *shape):
                raise ValueError(f"{name}: program shape {buf.shape}")
            for layer in range(buf.shape[0]):
                buf = _put_layer(buf, layer, draw(m, seed, name, layer))
        elif name == "embedding":
            if buf.shape[1:] != shape[1:] or buf.shape[0] < shape[0]:
                raise ValueError(f"{name}: program shape {buf.shape}")
            buf = _put_rows(buf, draw(m, seed, name))
        else:
            if buf.shape != shape:
                raise ValueError(f"{name}: program shape {buf.shape}")
            buf = _put_rows(buf, draw(m, seed, name))
        out.append(buf)
    return jax.tree_util.tree_unflatten(treedef, out)
