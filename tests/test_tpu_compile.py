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

@pytest.mark.parametrize("arch,b,h,hkv,hd,smax,layers", [
    pytest.param("qwen2-0.5b", 4, 14, 2, 64, 256, None,
                 id="qwen2-0.5b-4-14-2-64-256"),
    pytest.param("aiida-demo-110m", 4, 12, 4, 64, 256, None,
                 id="aiida-demo-110m-4-12-4-64-256"),
    # every KV head of a row in one block: granite's 16 stored heads of 64
    # and granite-4.0-h's 8 of 128 fit scoped VMEM at 512 positions
    pytest.param("granite-3-2b", 4, 32, 16, 64, 2048, 36,
                 id="granite-3-2b-4-32-16-64-2048-36"),
    pytest.param("granite-4.0-h-small", 4, 32, 8, 128, 2048, 2,
                 id="granite-4.0-h-small-4-32-8-128-2048-2"),
])
def test_decode_attention_compiles(one_chip, arch, b, h, hkv, hd, smax,
                                   layers):
    """The dense kernel at served widths; with ``layers``, on the stacked
    cache at a traced layer index, as the decode step calls it."""
    from repro.kernels.decode_attention.ops import decode_attention

    stack = () if layers is None else (layers,)

    def fn(q, k, v, lens, at):
        return decode_attention(q, k, v, lens, block_kv=512, interpret=False,
                                layer=None if layers is None else at)

    compiled = _compile(fn, _sds((b, h, hd), BF16, one_chip),
                        _sds(stack + (b, hkv, hd, smax), BF16, one_chip),
                        _sds(stack + (b, hkv, hd, smax), BF16, one_chip),
                        _sds((b,), I32, one_chip), _sds((), I32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_kv", [128, 512])
def test_latent_decode_attention_compiles_at_moonlight(one_chip, block_kv):
    """Moonlight's latent rows (512 + 64) for 16 heads, 4 slots of 2048:
    a width that is no multiple of 128 as a whole block, values sliced
    from its first 512 columns, block indices clamped by the lengths."""
    from repro.kernels.decode_attention.ops import latent_decode_attention

    def fn(q, cache, lens):
        return latent_decode_attention(q, cache, lens, scale=192 ** -0.5,
                                       value_dim=512, block_kv=block_kv,
                                       interpret=False)

    compiled = _compile(fn, _sds((4, 16, 576), BF16, one_chip),
                        _sds((4, 576, 2048), BF16, one_chip),
                        _sds((4,), I32, one_chip))
    assert "latent_decode_attention" in compiled.as_text()


#: (arch, layers): granite and qwen at their served widths (granite's 16
#: stored KV heads, qwen's 2), moonlight's latent cache with one dense and
#: one expert layer; 4 slots of 2048 positions each
IN_PLACE_STEPS = [("granite-3-2b", 4), ("qwen2-0.5b", 4),
                  ("moonlight-16b-a3b", 2)]

#: ops that may yield a buffer of the cache's size: the donated cache,
#: its in-place updates, and the loop and tuple plumbing around them.
#: ``copy-start``/``copy-done`` stage a single-layer stack in the chip's
#: fast memory and back, keeping its layout; a relayout is a ``copy``.
_CACHE_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while",
                   "bitcast", "dynamic-update-slice", "copy-start",
                   "copy-done"}


@pytest.mark.parametrize("arch,layers", IN_PLACE_STEPS)
def test_decode_step_updates_the_cache_in_place(one_chip, monkeypatch, arch,
                                                layers):
    """The decode step as the scheduler jits it (cache donated, one
    position per slot): no op but an in-place update yields a buffer of
    one layer's cache or of the whole stack (no copy, transpose or
    dynamic-slice of it), the output aliases the donated cache, and the
    step's temporaries are smaller than one layer's cache."""
    import functools
    import re

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.moe_decode import ops as md_ops
    from repro.models.registry import build, serving_params
    from repro.serving.serve import make_decode_step

    monkeypatch.setattr(da_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(md_ops, "interpret_default", lambda: False)
    cfg = get_config(arch).replace(decode_impl="pallas", num_layers=layers)
    if cfg.first_dense_layers:
        cfg = cfg.replace(first_dense_layers=1)
    bundle = build(cfg)
    b, max_len = 4, 2048

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(serving_params, cfg),
                                    bundle.param_shapes()))
    cache = on_chip(jax.eval_shape(lambda: bundle.init_cache(b, max_len)))
    compiled = jax.jit(make_decode_step(bundle), donate_argnums=(1,)).lower(
        params, cache, _sds((b, 1), I32, one_chip),
        _sds((b,), I32, one_chip)).compile()

    stacks = [leaf.shape for leaf in jax.tree.leaves(cache)]
    sizes = {tuple(sorted(d for d in s[i:] if d > 1))
             for s in stacks for i in (0, 1)}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(3) not in _CACHE_PLUMBING:
            dims = tuple(sorted(int(d) for d in m.group(2).split(",")
                                if d and int(d) > 1))
            if dims in sizes:
                found.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not found, found
    mem = compiled.memory_analysis()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(cache))
    layer_bytes = min(leaf.size * leaf.dtype.itemsize // leaf.shape[0]
                      for leaf in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_bytes


def test_hybrid_decode_step_updates_its_state_in_place(one_chip,
                                                      monkeypatch):
    """granite-4.0-h-small at its published widths, cut to its first six
    layers (Mamba 0-4, attention 5) with 9 held experts, 4 slots of 2048,
    as the scheduler jits the step: the Mamba decode kernel compiles
    inside it, the output aliases the whole donated cache (the float32
    state, the conv inputs and the KV rows), no op but an in-place update
    yields a buffer of one layer's state or of the stack, and the step's
    temporaries are smaller than one layer's state."""
    import functools
    import re

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.moe_decode import ops as md_ops
    from repro.kernels.ssm_decode import ops as sd_ops
    from repro.models.registry import build, serving_params
    from repro.serving.serve import make_scheduled_step

    monkeypatch.setattr(da_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(md_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(sd_ops, "interpret_default", lambda: False)
    cfg = get_config("granite-4.0-h-small").replace(
        decode_impl="pallas", num_layers=6, experts_held=9)
    bundle = build(cfg)
    b, max_len = 4, 2048

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(serving_params, cfg),
                                    bundle.param_shapes()))
    cache = on_chip(jax.eval_shape(lambda: bundle.init_cache(b, max_len)))
    assert cache["mamba0"]["ssm"].shape == (5, b, 128, 8192)
    compiled = jax.jit(make_scheduled_step(bundle), donate_argnums=(1,)) \
        .lower(params, cache, _sds((b, 1), I32, one_chip),
               _sds((b,), I32, one_chip),
               _sds((b,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    assert "ssm_decode" in text
    state = cache["mamba0"]["ssm"].shape
    sizes = {tuple(sorted(d for d in state[i:] if d > 1)) for i in (0, 1)}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(3) not in _CACHE_PLUMBING | {"custom-call"}:
            dims = tuple(sorted(int(d) for d in m.group(2).split(",")
                                if d and int(d) > 1))
            if dims in sizes:
                found.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not found, found
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < b * 128 * 8192 * 4


@pytest.mark.parametrize("arch,over", [
    ("moonlight-16b-a3b", dict(num_layers=3, experts_held=8)),
    ("granite-4.0-h-small", dict(num_layers=6, experts_held=9)),
    ("kimi-linear-48b-a3b", dict(num_layers=4, experts_held=16)),
])
def test_expert_decode_step_reads_the_expert_stacks_in_place(
        one_chip, monkeypatch, arch, over):
    """The expert configurations at their published widths, as the
    scheduler jits the decode step: the held-expert layer is the
    ``moe_decode`` kernel, no op but the kernel's call yields a layer's
    expert matrices or the whole stack (no dynamic-slice of it), and the
    step returns the held experts it read."""
    import functools
    import re

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.kda_decode import ops as kd_ops
    from repro.kernels.moe_decode import ops as md_ops
    from repro.kernels.ssm_decode import ops as sd_ops
    from repro.models.registry import build, serving_params
    from repro.serving.serve import make_scheduled_step

    for ops in (da_ops, md_ops, sd_ops, kd_ops):
        monkeypatch.setattr(ops, "interpret_default", lambda: False)
    cfg = get_config(arch).replace(decode_impl="pallas", **over)
    bundle = build(cfg)
    b, max_len = 4, 2048

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(serving_params, cfg),
                                    bundle.param_shapes()))
    cache = on_chip(jax.eval_shape(lambda: bundle.init_cache(b, max_len)))
    lowered = jax.jit(make_scheduled_step(bundle), donate_argnums=(1,)) \
        .lower(params, cache, _sds((b, 1), I32, one_chip),
               _sds((b,), I32, one_chip), _sds((b,), jnp.bool_, one_chip))
    assert len(lowered.out_info) == 4
    text = lowered.compile().as_text()
    assert "moe_decode" in text
    n, d, f = cfg.held_experts, cfg.d_model, cfg.moe_d_ff
    # one expert's (D, F): not where the shared expert has the same
    # shape (kimi-linear), whose matrices the scan hands each layer
    sizes = {tuple(sorted(s)) for s in ((n, d, f), (d, f))
             if len(s) == 3 or cfg.shared_d_ff != f}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(3) not in _CACHE_PLUMBING | {"custom-call"}:
            dims = tuple(sorted(int(x) for x in m.group(2).split(",")
                                if x and int(x) > 1))
            if dims in sizes or dims[-3:] == tuple(sorted((n, d, f))):
                found.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not found, found


@pytest.mark.parametrize("heads_block", [4, 8])
def test_kda_decode_compiles_at_kimi_linear(one_chip, heads_block):
    """Kimi-Linear's KDA state, 32 heads of 128 for 4 slots in a stack of
    3 layers: row blocks of q, k and the decay, column blocks of v and the
    output, the state's block read at a scalar-prefetched layer and
    written back in place."""
    from repro.kernels.kda_decode.ops import kda_decode

    def fn(q, k, v, g, beta, state, layer):
        return kda_decode(q, k, v, g, beta, state, layer,
                          heads_block=heads_block, interpret=False)

    row = _sds((4, 32, 128), F32, one_chip)
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        row, row, row, row, _sds((4, 32), F32, one_chip),
        _sds((3, 4, 32, 128, 128), F32, one_chip),
        _sds((), I32, one_chip)).compile()
    assert "kda_decode" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == \
        3 * 4 * 32 * 128 * 128 * 4


def test_kda_decode_step_updates_its_state_in_place(one_chip, monkeypatch):
    """kimi-linear-48b-a3b at its published widths, cut to its first four
    layers (KDA 0 with the dense MLP, KDA 1-2 and MLA 3 with 16 held
    experts), 4 slots of 2048, as the scheduler jits the step: the KDA,
    latent and expert kernels compile inside it, the output aliases the
    whole donated cache (the float32 states, the conv inputs and the
    latent rows), no op but an in-place update yields a buffer of one
    layer's state or of the stack, and the step's temporaries are smaller
    than one layer's state."""
    import functools
    import re

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.kda_decode import ops as kd_ops
    from repro.kernels.moe_decode import ops as md_ops
    from repro.models.registry import build, serving_params
    from repro.serving.serve import make_scheduled_step

    for ops in (da_ops, md_ops, kd_ops):
        monkeypatch.setattr(ops, "interpret_default", lambda: False)
    cfg = get_config("kimi-linear-48b-a3b").replace(
        decode_impl="pallas", num_layers=4, experts_held=16)
    bundle = build(cfg)
    b, max_len = 4, 2048

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(serving_params, cfg),
                                    bundle.param_shapes()))
    cache = on_chip(jax.eval_shape(lambda: bundle.init_cache(b, max_len)))
    assert cache["kda1"]["state"].shape == (2, b, 32, 128, 128)
    compiled = jax.jit(make_scheduled_step(bundle), donate_argnums=(1,)) \
        .lower(params, cache, _sds((b, 1), I32, one_chip),
               _sds((b,), I32, one_chip),
               _sds((b,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    for kernel in ("kda_decode", "latent_decode_attention", "moe_decode"):
        assert kernel in text, kernel
    state = cache["kda1"]["state"].shape
    sizes = {tuple(sorted(d for d in state[i:] if d > 1)) for i in (0, 1)}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(3) not in _CACHE_PLUMBING | {"custom-call"}:
            dims = tuple(sorted(int(d) for d in m.group(2).split(",")
                                if d and int(d) > 1))
            if dims in sizes:
                found.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not found, found
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < b * 32 * 128 * 128 * 4


@pytest.mark.parametrize("roomy", [False, True])
def test_kimi_prefill_compiles_at_1536_with_the_roomy_options(one_chip,
                                                               roomy):
    """kimi-linear-48b-a3b's prefill of a 1536-token prompt at its
    published widths, cut to its first four layers: at the compiler's
    default scoped VMEM of 16 MiB it is refused (the expert layer's row
    gather, 12288 rows of 2304, asks for 16.41 MiB), and at the
    configuration's ``prefill_vmem_kib``, as the scheduler compiles it, it
    compiles. A compiler that stops refusing the default makes the setting
    unneeded."""
    import functools

    from repro.configs import get_config
    from repro.models.registry import build, serving_params
    from repro.serving.serve import make_prefill_step

    cfg = get_config("kimi-linear-48b-a3b").replace(
        decode_impl="pallas", num_layers=4, experts_held=16)
    bundle = build(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(serving_params, cfg),
                                    bundle.param_shapes()))
    row = on_chip(jax.eval_shape(lambda: bundle.init_cache(1, 2048)))
    lowered = jax.jit(make_prefill_step(bundle)).lower(
        params, {"tokens": _sds((1, 1536), I32, one_chip)}, row)
    if not roomy:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="memory space vmem"):
            lowered.compile()
        return
    compiled = lowered.compile(
        {"xla_tpu_scoped_vmem_limit_kib": cfg.prefill_vmem_kib})
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


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
