"""Sharded model checkpoints (elastic restore, async) + data pipeline
determinism and exact resume."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import checkpoint as ckpt
from repro.training.data import DataConfig, TokenStream


def test_checkpoint_roundtrip(tmp_path):
    state = {
        "step": jnp.asarray(7),
        "params": {"w": jnp.arange(12.0).reshape(3, 4),
                   "b": jnp.ones((4,))},
    }
    path = ckpt.save_checkpoint(str(tmp_path), 7, state)
    assert os.path.exists(os.path.join(path, "manifest.json"))
    restored = ckpt.restore_checkpoint(str(tmp_path), target=state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_latest(tmp_path):
    state = {"x": jnp.zeros((2,))}
    for step in (1, 2, 3, 4, 5):
        ckpt.save_checkpoint(str(tmp_path), step, state, max_to_keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_4", "step_5"]


def test_async_checkpointer_overlaps(tmp_path):
    state = {"x": jnp.arange(1000.0)}
    ac = ckpt.AsyncCheckpointer(str(tmp_path))
    ac.save(1, state)
    ac.save(2, state)     # barriers on the first
    ac.wait()
    assert ckpt.latest_step(str(tmp_path)) == 2
    restored = ckpt.restore_checkpoint(str(tmp_path), target=state)
    np.testing.assert_allclose(np.asarray(restored["x"]),
                               np.asarray(state["x"]))


def test_data_pipeline_deterministic():
    cfg = DataConfig(vocab_size=1000, seq_len=64, batch_size=4, seed=3)
    s1, s2 = TokenStream(cfg), TokenStream(cfg)
    for _ in range(3):
        b1, b2 = s1.next_batch(), s2.next_batch()
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 64)
    assert (b1["tokens"] >= 0).all() and (b1["tokens"] < 1000).all()
    # labels are tokens shifted by one position
    full1 = np.concatenate([b1["tokens"], b1["labels"][:, -1:]], axis=1)
    np.testing.assert_array_equal(full1[:, 1:], b1["labels"])


def test_data_pipeline_exact_resume():
    cfg = DataConfig(vocab_size=1000, seq_len=32, batch_size=2, seed=5)
    s1 = TokenStream(cfg)
    for _ in range(2):
        s1.next_batch()
    cursor = s1.state_dict()
    expected = s1.next_batch()

    s2 = TokenStream(cfg)
    s2.load_state_dict(cursor)
    resumed = s2.next_batch()
    np.testing.assert_array_equal(expected["tokens"], resumed["tokens"])


def test_data_pipeline_host_sharding_disjoint():
    """Different hosts consume disjoint document streams."""
    kw = dict(vocab_size=500, seq_len=32, batch_size=2, seed=1, num_hosts=2)
    h0 = TokenStream(DataConfig(host_id=0, **kw))
    h1 = TokenStream(DataConfig(host_id=1, **kw))
    b0, b1 = h0.next_batch(), h1.next_batch()
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_elastic_restore_resharding(tmp_path):
    """A checkpoint saved unsharded restores onto explicit shardings (the
    single-device analogue of resuming on a different mesh size)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import make_mesh

    mesh = make_mesh((1,), ("data",))
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save_checkpoint(str(tmp_path), 1, state)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored = ckpt.restore_checkpoint(str(tmp_path), target=state,
                                       shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(state["w"]))
