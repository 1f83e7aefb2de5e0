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
    import jax
    from repro.launch import dryrun as dr

    # shrink the production mesh for the in-test lowering
    import repro.launch.mesh as mesh_mod
    from repro.configs import make_mesh
    def small_mesh(*, multi_pod=False):
        if multi_pod:
            return make_mesh((2, 2, 2), ("pod", "data", "model"))
        return make_mesh((2, 4), ("data", "model"))
    mesh_mod.make_production_mesh = small_mesh
    dr.make_production_mesh = small_mesh

    # reduced config so the compile is fast
    from repro.configs import reduced_config
    import repro.launch.dryrun as d2
    d2.get_config = lambda a: reduced_config(a)

    res = dr.lower_cell({arch!r}, {shape!r}, multi_pod={multi!r})
    print("RESULT:" + json.dumps({{
        "ok": "error" not in res and not res.get("skipped"),
        "collectives": res.get("collectives", {{}}).get("counts"),
    }}))
""")


@pytest.mark.parametrize("arch,shape,multi", [
    ("qwen3-4b", "train_4k", False),
    ("moonshot-v1-16b-a3b", "train_4k", True),
    ("recurrentgemma-2b", "decode_32k", False),
])
def test_real_lowering_on_8_fake_devices(arch, shape, multi):
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = SUBPROCESS_PROG.format(src=os.path.abspath(src), arch=arch,
                                  shape=shape, multi=multi)
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    result = json.loads(line[0][len("RESULT:"):])
    assert result["ok"], proc.stdout


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
