"""Compile-only checks of the Pallas kernels and the sharded decode step
for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a ``v5e:2x2``
topology that it describes without hardware. These tests catch what the
interpret-mode tests in ``test_kernels.py`` cannot: block layouts the
chip refuses, unsupported in-kernel primitives, and kernels that cannot
be partitioned under a mesh. Nothing runs, so results are not checked.

The topology is described inside a fixture: only one process at a time
may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep it out of the way
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 - any failure: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------------------
# decode attention (the serving path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,b,h,hkv,hd,smax", [
    ("qwen2-0.5b", 4, 14, 2, 64, 256),
    ("aiida-demo-110m", 4, 12, 4, 64, 256),
])
def test_decode_attention_compiles(one_chip, arch, b, h, hkv, hd, smax):
    from repro.kernels.decode_attention.ops import decode_attention

    def fn(q, k, v, lens):
        return decode_attention(q, k, v, lens, block_kv=512, interpret=False)

    compiled = _compile(fn, _sds((b, h, hd), BF16, one_chip),
                        _sds((b, smax, hkv, hd), BF16, one_chip),
                        _sds((b, smax, hkv, hd), BF16, one_chip),
                        _sds((b,), I32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_decode_step_compiles_on_four_chips(topo, monkeypatch):
    """aiida-demo-110m at its published width, heads sharded over
    model=4: the decode kernel must sit inside the partitioned program."""
    from repro.configs import get_config, make_mesh
    from repro.distributed.sharding import make_rules, tree_named_shardings
    from repro.kernels.decode_attention import ops as da_ops
    from repro.models.common import axis_rules
    from repro.models.registry import build
    from repro.serving.serve import make_decode_step

    # the program asks the backend (CPU here) whether to interpret
    monkeypatch.setattr(da_ops, "interpret_default", lambda: False)
    cfg = get_config("aiida-demo-110m").replace(decode_impl="pallas")
    bundle = build(cfg)
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    rules = make_rules(cfg, mesh, fsdp=False)
    b, max_len = 4, 256
    with axis_rules(mesh, rules):
        p_sh = tree_named_shardings(bundle.param_shapes(), bundle.param_axes(),
                                    rules, mesh)
        cache_shapes = jax.eval_shape(lambda: bundle.init_cache(b, max_len))
        c_sh = tree_named_shardings(cache_shapes, bundle.cache_axes(),
                                    rules, mesh)
        params = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                              bundle.param_shapes(), p_sh)
        cache = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                             cache_shapes, c_sh)
        rep = NamedSharding(mesh, P())
        compiled = jax.jit(make_decode_step(bundle)).lower(
            params, cache, _sds((b, 1), I32, rep),
            _sds((b,), I32, rep)).compile()
    assert rules["kv_heads_sharded"] == "model"
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# training-path kernels at real widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_fwd_bwd_compile(one_chip, hd):
    from repro.kernels.flash_attention.ops import flash_attention

    b, s, h, hkv = 1, 1024, 8, 2

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=512,
                              block_kv=512, interpret=False)
        return jnp.sum(out.astype(F32))

    args = (_sds((b, s, h, hd), BF16, one_chip),
            _sds((b, s, hkv, hd), BF16, one_chip),
            _sds((b, s, hkv, hd), BF16, one_chip))
    fwd = _compile(loss, *args)
    bwd = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert "tpu_custom_call" in fwd.as_text()
    assert bwd.as_text().count("tpu_custom_call") >= 3


def test_mlstm_chunk_compiles_at_xlstm_350m(one_chip):
    from repro.configs import get_config
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk

    cfg = get_config("xlstm-350m")
    h = cfg.num_heads
    hd = int(cfg.d_model * cfg.mlstm_proj_factor) // h
    b, s = 1, 2 * cfg.mlstm_chunk

    def fn(q, k, v, li, lf, C0, n0, m0):
        return mlstm_chunk(q, k, v, li, lf, C0, n0, m0,
                           chunk=cfg.mlstm_chunk, interpret=False)

    compiled = _compile(fn,
                        _sds((b, h, s, hd), BF16, one_chip),
                        _sds((b, h, s, hd), BF16, one_chip),
                        _sds((b, h, s, hd), BF16, one_chip),
                        _sds((b, h, s), F32, one_chip),
                        _sds((b, h, s), F32, one_chip),
                        _sds((b, h, hd, hd), F32, one_chip),
                        _sds((b, h, hd), F32, one_chip),
                        _sds((b, h), F32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_at_recurrentgemma_2b(one_chip):
    from repro.configs import get_config
    from repro.kernels.rglru_scan.ops import rglru_scan

    d = get_config("recurrentgemma-2b").d_rnn
    b, s = 2, 256

    def loss(a, x, h0):
        hs, h_last = rglru_scan(a, x, h0, interpret=False)
        return jnp.sum(hs) + jnp.sum(h_last)

    args = (_sds((b, s, d), F32, one_chip), _sds((b, s, d), F32, one_chip),
            _sds((b, d), F32, one_chip))
    fwd = _compile(loss, *args)
    bwd = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert "tpu_custom_call" in fwd.as_text()
    assert "tpu_custom_call" in bwd.as_text()
