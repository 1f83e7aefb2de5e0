"""The architecture module that a configuration names is the one place
that knows its architecture: the harness takes the weight leaves, the
program check and the counts of useful work from it. Checked on the dense
module and the configurations that use it, and on a test-only module
added to a throwaway tree as a new file."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

import tiny
from benchlib import flops, peaks, xtrace
from benchlib.spec import Spec

DATA = Path(__file__).resolve().parent / "data" / "decode.xplane.pb"
DENSE = Spec(tiny.ROOT).reference("dense_lm")

#: name -> (per-layer shape, std, mean, depth, vocabulary axis), as the
#: harness drew them before the leaf table moved into the module
TABLES = {
    "qwen2-0.5b": {
        "embedding": ((151936, 896), 0.03340765523905305, 0.0, 0, 0),
        "ln_final": ((896,), 0.05, 1.0, 0, None),
        "layers.ln_attn": ((896,), 0.05, 1.0, 24, None),
        "layers.ln_mlp": ((896,), 0.05, 1.0, 24, None),
        "layers.attn.wq": ((896, 14, 64), 0.03340765523905305, 0.0, 24, None),
        "layers.attn.wk": ((896, 2, 64), 0.03340765523905305, 0.0, 24, None),
        "layers.attn.wv": ((896, 2, 64), 0.03340765523905305, 0.0, 24, None),
        "layers.attn.wo": ((14, 64, 896), 0.03340765523905305, 0.0, 24, None),
        "layers.mlp.w_gate": ((896, 4864), 0.03340765523905305, 0.0, 24,
                              None),
        "layers.mlp.w_up": ((896, 4864), 0.03340765523905305, 0.0, 24, None),
        "layers.mlp.w_down": ((4864, 896), 0.014338483366910109, 0.0, 24,
                              None),
        "layers.attn.bq": ((14, 64), 0.1, 0.0, 24, None),
        "layers.attn.bk": ((2, 64), 0.1, 0.0, 24, None),
        "layers.attn.bv": ((2, 64), 0.1, 0.0, 24, None),
    },
    "granite-3-2b": {
        "embedding": ((49155, 2048), 0.0018414239093399673, 0.0, 0, 0),
        "ln_final": ((2048,), 0.05, 1.0, 0, None),
        "layers.ln_attn": ((2048,), 0.05, 1.0, 36, None),
        "layers.ln_mlp": ((2048,), 0.05, 1.0, 36, None),
        "layers.attn.wq": ((2048, 32, 64), 0.0625, 0.0, 36, None),
        "layers.attn.wk": ((2048, 8, 64), 0.0625, 0.0, 36, None),
        "layers.attn.wv": ((2048, 8, 64), 0.022097086912079608, 0.0, 36,
                           None),
        "layers.attn.wo": ((32, 64, 2048), 0.022097086912079608, 0.0, 36,
                           None),
        "layers.mlp.w_gate": ((2048, 8192), 0.022097086912079608, 0.0, 36,
                              None),
        "layers.mlp.w_up": ((2048, 8192), 0.022097086912079608, 0.0, 36,
                            None),
        "layers.mlp.w_down": ((8192, 2048), 0.011048543456039804, 0.0, 36,
                              None),
    },
}


def _config(name):
    return json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(TABLES))
def test_dense_leaf_table(name):
    specs = DENSE.leaf_specs(_config(name))
    assert {k: tuple(v) for k, v in specs.items()} == TABLES[name]


@pytest.mark.parametrize("name", sorted(TABLES))
def test_dense_counts_are_flops_over_every_layer(name):
    m = _config(name)
    layers = m["num_hidden_layers"]
    for prompt, new in ((1, 1), (32, 16), (1792, 128)):
        assert DENSE.generate_flops(m, prompt, new) == \
            flops.generate_flops(m, prompt, new)
    for context in (1, 100, 1919):
        ops, nbytes = flops.decode_attention_work(m, context)
        assert DENSE.decode_attention_work(m, context) == \
            (ops * layers, nbytes * layers)


def _traced(run):
    """Gives a CPU run the recorded TPU trace, so that the device readers
    have something to read; they count the run's own calls."""
    run.trace = xtrace.load(str(DATA))
    win = run.trace.spans("bench.window")[0]
    run.trace_window = (win.start, win.end)
    run.traced_calls = run.calls
    run.peaks = peaks.peaks("TPU v5 lite")
    return run


@pytest.mark.parametrize("name", sorted(TABLES))
def test_readers_count_what_flops_counts(name):
    """The readers reach the module's counts; with the dense module they
    read what the shared counts of ``benchlib/flops.py`` give."""
    from benchlib import readers

    m = _config(name)
    calls = [types.SimpleNamespace(prompt_len=p, new_tokens=128, hit=False,
                                   tokens=[0] * 128) for p in (1024, 1792)]
    run = _traced(types.SimpleNamespace(model=m, arch=DENSE, calls=calls))
    lo, hi = run.trace_window
    work = sum(flops.generate_flops(m, c.prompt_len, c.new_tokens)
               for c in calls)
    assert readers.mfu(run) == \
        100.0 * work / ((hi - lo) * 1e-9) / run.peaks["bf16_flops"]
    assert readers.decode_attn_roofline(run) is not None


PROBE = '''"""Test-only architecture: the dense module's, with a program check of
its own and three times the dense counts."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "probe_dense", Path(__file__).with_name("dense_lm.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)
logits_at, leaf_specs = dense.logits_at, dense.leaf_specs
FACTOR = 3


def check_program(cfg, m):
    wrong = dense.check_program(cfg, m)
    if cfg.num_experts != m.get("num_experts", 0):
        wrong["num_experts"] = (cfg.num_experts, m["num_experts"])
    return wrong


def generate_flops(m, prompt, new_tokens):
    return FACTOR * dense.generate_flops(m, prompt, new_tokens)


def decode_attention_work(m, context):
    ops, nbytes = dense.decode_attention_work(m, context)
    return FACTOR * ops, FACTOR * nbytes
'''


@pytest.fixture(scope="module")
def probe_root(tmp_path_factory):
    root = tiny.make_tree(tmp_path_factory.mktemp("arch"))
    (root / "bench" / "refs" / "probe_lm.py").write_text(PROBE)
    tiny.add_config(root, {**tiny.TINY, "name": "probe",
                           "reference": "probe_lm"})
    tiny.add_config(root, {**tiny.TINY, "name": "probe-moe",
                           "reference": "probe_lm", "num_experts": 8})
    return root


def test_a_mismatch_the_module_reports_stops_the_run_before_any_call(
        probe_root, monkeypatch):
    from repro.engine import launch
    from repro.serving import inference

    reached = []
    for mod, name in ((inference, "get_engine"), (launch, "run_get_node")):
        monkeypatch.setattr(mod, name,
                            lambda *a, _n=name, **k: reached.append(_n))
    with pytest.raises(ValueError, match="num_experts"):
        tiny.run(probe_root, "t.probe-moe.tinyqa")
    assert reached == []


def test_readers_count_with_the_module(probe_root):
    import run as bench_run
    from benchlib import readers

    _spec, run, _device = bench_run.run_cell(
        probe_root, "t.probe.tinyqa", 5, 2.0, False, require_chip=False,
        t_start=0.0)
    assert run.arch.FACTOR == 3 and bench_run.correct(run.checks)
    misses = [c for c in run.calls if not c.hit]
    assert misses
    _traced(run)
    lo, hi = run.trace_window
    work = sum(run.arch.generate_flops(run.model, c.prompt_len, c.new_tokens)
               for c in misses)
    assert work == 3 * sum(flops.generate_flops(
        run.model, c.prompt_len, c.new_tokens) for c in misses)
    assert readers.mfu(run) == \
        100.0 * work / ((hi - lo) * 1e-9) / run.peaks["bf16_flops"]
    probe = readers.decode_attn_roofline(run)
    run.arch = DENSE
    assert probe == pytest.approx(3 * readers.decode_attn_roofline(run),
                                  rel=1e-12)
