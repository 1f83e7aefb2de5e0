"""The generic weight writer: leaves of any depth, a vocabulary axis
padded by the program, and a tree that does not hold what the
architecture module declares."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import weights
from benchlib.weights import Leaf

SEED = 2**31 + 11
SPECS = {
    "embedding": Leaf((6, 4), 0.5, 0.0, vocab_axis=0),
    "lm_head": Leaf((4, 6), 0.5, 0.0, vocab_axis=1),
    "ln_final": Leaf((4,), 0.05, 1.0),
    "dense_layers.w": Leaf((4, 5), 0.3, 0.0, 1),
    "layers.w": Leaf((4, 3), 0.2, 0.0, 3),
    "layers.b": Leaf((3,), 0.1, 0.0, 3),
}


def _tree(**shapes):
    """The program's tree: padded vocabulary (8 of 6), stacks of 1 and 3,
    filled with ones so that what is not written shows."""
    shape = {"embedding": (8, 4), "lm_head": (4, 8), "ln_final": (4,),
             "dense_layers.w": (1, 4, 5), "layers.w": (3, 4, 3),
             "layers.b": (3, 3), **shapes}
    tree = {}
    for name, s in shape.items():
        if s is None:
            continue
        *outer, leaf = name.split(".")
        node = tree
        for key in outer:
            node = node.setdefault(key, {})
        node[leaf] = jnp.ones(s, jnp.bfloat16)
    return tree


def test_each_layer_of_each_stack_holds_its_own_draw():
    out = weights.overwrite(_tree(), SPECS, SEED)

    def want(name, layer=0):
        return np.asarray(weights.draw(SPECS, SEED, name, layer)
                          .astype(jnp.bfloat16), np.float32)

    got = {weights.path_name(p): np.asarray(v, np.float32) for p, v in
           jax.tree_util.tree_flatten_with_path(out)[0]}
    assert got["dense_layers.w"].shape == (1, 4, 5)
    np.testing.assert_array_equal(got["dense_layers.w"][0],
                                  want("dense_layers.w"))
    for name in ("layers.w", "layers.b"):
        for layer in range(3):
            np.testing.assert_array_equal(got[name][layer], want(name, layer))
        assert not np.array_equal(got[name][0], got[name][1])
    # two names never share a draw, whatever their depth
    assert not np.array_equal(want("layers.w", 0)[:, :3],
                              want("dense_layers.w", 0)[:, :3])
    np.testing.assert_array_equal(got["ln_final"], want("ln_final"))
    np.testing.assert_array_equal(got["embedding"][:6], want("embedding"))
    np.testing.assert_array_equal(got["lm_head"][:, :6], want("lm_head"))
    # the padded vocabulary is zero, on whichever axis it lies
    assert not got["embedding"][6:].any()
    assert not got["lm_head"][:, 6:].any()


@pytest.mark.parametrize("shapes, message", [
    ({"dense_layers.w": (3, 4, 5)}, "dense_layers.w"),   # wrong depth
    ({"layers.b": (1, 3)}, "layers.b"),                  # wrong depth
    ({"layers.w": (3, 4, 4)}, "layers.w"),               # a stacked leaf padded
    ({"lm_head": (4, 5)}, "lm_head"),                    # vocabulary short
    ({"lm_head": (5, 8)}, "lm_head"),                    # padded off its axis
    ({"layers.b": None}, "not the reference's"),         # a leaf missing
    ({"layers.extra": (3, 2)}, "not the reference's"),   # a leaf undeclared
])
def test_a_tree_that_is_not_the_declared_one_raises(shapes, message):
    with pytest.raises(ValueError, match=message):
        weights.overwrite(_tree(**shapes), SPECS, SEED)
