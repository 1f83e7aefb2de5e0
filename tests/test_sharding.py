"""Sharding-rule resolution + an actual 8-device lowering in a subprocess
(the main test process keeps the single CPU device)."""

import json
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config
from repro.distributed import sharding as sh


class FakeMesh:
    """Just enough mesh for rule resolution (axis_names + devices.shape)."""

    def __init__(self, shape, names):
        import numpy as np
        self.axis_names = names
        self.devices = np.zeros(shape)


def test_rules_head_tp_arch():
    cfg = get_config("deepseek-67b")
    mesh = FakeMesh((16, 16), ("data", "model"))
    rules = sh.make_rules(cfg, mesh, fsdp=True)
    assert rules["heads"] == "model"
    assert rules["embed"] == ("data",)
    assert rules["seq_sharded"] is None          # head-TP archs don't seq-shard


def test_rules_seq_parallel_arch():
    cfg = get_config("qwen2-0.5b")
    mesh = FakeMesh((16, 16), ("data", "model"))
    rules = sh.make_rules(cfg, mesh, fsdp=False)
    assert rules["heads"] is None                 # 14 heads can't shard 16 ways
    assert rules["seq_sharded"] == "model"
    assert rules["embed"] is None                 # fsdp off => replicated


def test_rules_moe_strategies():
    mesh = FakeMesh((16, 16), ("data", "model"))
    ep = sh.make_rules(get_config("moonshot-v1-16b-a3b"), mesh)
    assert ep["expert_sharded"] == "model" and ep["moe_ffn"] is None
    tp = sh.make_rules(get_config("grok-1-314b"), mesh)
    assert tp["expert_sharded"] is None and tp["moe_ffn"] == "model"


def test_divisibility_fallback_replicates():
    from jax.sharding import PartitionSpec as P
    notes = []
    spec = sh.resolve_spec((7, 128), ("batch", "ffn"),
                           {"batch": ("data",), "ffn": "model"},
                           {"data": 16, "model": 16}, notes, "w")
    assert spec == P(None, "model")               # 7 % 16 != 0 -> replicated
    assert notes and "7" in notes[0]


def test_multi_pod_batch_axes():
    cfg = get_config("qwen3-4b")
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    rules = sh.make_rules(cfg, mesh, fsdp=True, fsdp_over_pod=True)
    assert rules["batch"] == ("pod", "data")
    assert rules["embed"] == ("pod", "data")


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import json
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import make_mesh, reduced_config
    from repro.distributed.sharding import make_rules, tree_named_shardings
    from repro.models.common import axis_rules
    from repro.models.registry import SHAPES, build, serving_params
    from repro.serving.serve import make_decode_step
    from repro.training.train_step import (
        TrainConfig, make_train_step, train_state_axes, train_state_shapes)

    arch, kind, multi = {arch!r}, {kind!r}, {multi!r}
    mesh = (make_mesh((2, 2, 2), ("pod", "data", "model")) if multi
            else make_mesh((2, 4), ("data", "model")))
    # reduced config so the compile is fast
    cfg = reduced_config(arch)
    if kind == "serve":
        cfg = cfg.replace(decode_impl="pallas")
    bundle = build(cfg)
    rules = make_rules(cfg, mesh, fsdp=False)

    def sharded(struct, axes):
        return tree_named_shardings(struct, axes, rules, mesh)

    with axis_rules(mesh, rules):
        if kind == "train_4k":
            cell = SHAPES[kind]
            tcfg = TrainConfig()
            state = train_state_shapes(bundle, tcfg)
            state_sh = sharded(state, train_state_axes(bundle, tcfg))
            batch = bundle.batch_struct(cell)
            step = jax.jit(make_train_step(bundle, tcfg),
                           in_shardings=(state_sh,
                                         sharded(batch,
                                                 bundle.batch_axes(cell))),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))
            lowered = step.lower(state, batch)
        else:
            # decode_32k: the cell's batch and cache, one position for all
            # rows; serve: the scheduler's step, 4 slots at their own
            # positions over the weights as served
            if kind == "serve":
                b, max_len = 4, 2048
                params = jax.eval_shape(functools.partial(serving_params, cfg),
                                        bundle.param_shapes())
                pos = jax.ShapeDtypeStruct((b,), jnp.int32)
            else:
                cell = SHAPES[kind]
                b, max_len = cell.global_batch, cell.seq_len
                params = bundle.param_shapes()
                pos = jax.ShapeDtypeStruct((), jnp.int32)
            cache = jax.eval_shape(lambda: bundle.init_cache(b, max_len))
            cache_sh = sharded(cache, bundle.cache_axes())
            tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            step = jax.jit(make_decode_step(bundle),
                           in_shardings=(sharded(params, bundle.param_axes()),
                                         cache_sh,
                                         sharded(tokens, ("batch", None)),
                                         NamedSharding(mesh, P())),
                           out_shardings=(None, cache_sh),
                           donate_argnums=(1,))
            lowered = step.lower(params, cache, tokens, pos)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    print("RESULT:" + json.dumps({{
        "collectives": {{op: hlo.count(" " + op + "(")
                        for op in ("all-gather", "all-reduce",
                                   "reduce-scatter", "all-to-all",
                                   "collective-permute")}},
    }}))
""")


@pytest.mark.parametrize("arch,kind,multi", [
    ("qwen3-4b", "train_4k", False),
    ("moonshot-v1-16b-a3b", "train_4k", True),
    ("recurrentgemma-2b", "decode_32k", False),
    # served decode steps of benchmarked configurations: sequence-sharded
    # attention with the kernel's bare call, and the latent cache with the
    # ragged expert layer
    ("qwen2-0.5b", "serve", False),
    ("moonlight-16b-a3b", "serve", False),
])
def test_real_lowering_on_8_fake_devices(arch, kind, multi):
    """Lower and compile one step under make_rules' shardings on a (2, 4)
    or (2, 2, 2) mesh of 8 host devices: a train step, a decode step at a
    SHAPES cell, or the scheduler's served decode step."""
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = SUBPROCESS_PROG.format(src=os.path.abspath(src), arch=arch,
                                  kind=kind, multi=multi)
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    result = json.loads(line[0][len("RESULT:"):])
    # a sharded program exchanges something between devices
    assert sum(result["collectives"].values()) > 0, result


def test_make_mesh_has_auto_axes():
    from jax.sharding import AxisType

    from repro.configs import make_mesh, make_serving_mesh

    assert make_mesh((1,), ("data",)).axis_types == (AxisType.Auto,)
    assert make_serving_mesh(1, 1).axis_types == (AxisType.Auto,) * 2


def test_setup_devices_pins_no_platform_by_default():
    import jax

    from repro.configs import setup_devices

    before = jax.config.read("jax_platform_name")
    assert setup_devices() == jax.devices()
    assert jax.config.read("jax_platform_name") == before
