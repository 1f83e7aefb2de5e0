"""The Kimi-Linear architecture module (``refs/kimi_linear_lm.py``) and
the cell of Kimi-Linear-48B-A3B: its leaves are the program's tree leaf
for leaf, its program check, its counts by hand, its KDA-kernel reader,
and a whole tiny run through the harness that an altered token and a
wrong cache hit make not correct, and the mean-gap check that tells the
program from the control."""

from __future__ import annotations

import json
import math
import types

import jax
import pytest

import tiny
from benchlib import peaks, weights, xtrace
from benchlib.spec import Spec

REF = Spec(tiny.ROOT).reference("kimi_linear_lm")
CELL_NAME = "gen.kimi-linear-48b-a3b.docqa"
#: ``reduced_config("kimi-linear-48b-a3b")`` as a configuration file
#: states it: chip 1 of 2, holding experts 4-7 of 8
TINY_KIMI = {
    "name": "tiny-kimi", "reference": "kimi_linear_lm",
    "model_type": "kimi_linear", "num_hidden_layers": 4,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_a_layernorm_eps": 1e-6, "q_lora_rank": None,
    "mla_use_nope": True, "first_k_dense_replace": 1,
    "moe_intermediate_size": 64, "num_shared_experts": 2,
    "num_experts": 4, "num_experts_per_token": 3,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "tie_word_embeddings": False, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"full_attn_layers": [3], "kda_layers": [1, 2, 4],
                           "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "published": {"num_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 1, "first_expert": 4},
    "program": {"arch": "kimi-linear-48b-a3b", "reduced": True,
                "dtype": "bfloat16", "param_dtype": "float32",
                "kv_cache_dtype": "bfloat16", "decode_impl": "pallas"},
}
CELL = "t.tiny-kimi.tinyqa"
#: the tiny cells' limits. Read over every window (each count of finished
#: calls, from one to about 30) for seeds 3, 5, 977 and 2**31 + 7 (CPU
#: runs, the mean-gap mix's 32 new tokens): the program's mean gap reached
#: at most 0.053 and the control's fell to 0.196 at the least, so the mean
#: separates them; their widest gaps overlap (the program's up to 1.87, up
#: to 2.07 with tinyqa's 8 new tokens; the control's down to 0.74), as
#: moonlight's do, so the widest is a backstop above the program's
MEAN_LIMITS = {"logit_gap": 2.5, "logit_gap_mean": 0.12}
MEAN_CELL = "t.tiny-kimi.tinyqa_mean_gap"


def _file():
    return json.loads((tiny.BENCH / "configs" /
                       "kimi-linear-48b-a3b.json").read_text())


def _program(m):
    from entries.generate import program_config

    return program_config(m, REF)


@pytest.fixture
def published(monkeypatch):
    """The program's config for the published file; its overrides set the
    registered config, which is restored after the test."""
    import repro.configs.kimi_linear_48b_a3b as mod

    monkeypatch.setattr(mod, "CONFIG", mod.CONFIG)
    return _program(_file())


def test_leaves_are_the_program_tree_leaf_for_leaf():
    from repro.models.registry import build

    cfg = _program(TINY_KIMI)
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    specs = REF.leaf_specs(TINY_KIMI)
    out = weights.overwrite(params, specs, 11)   # raises on any mismatch
    names = {weights.path_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(out)[0]}
    assert names == set(specs)
    assert specs["kda0.mlp.w_gate"].shape == (128, 256)
    assert specs["kda1.moe.w_gate"].shape == (4, 128, 64)
    assert specs["kda1.moe.router"].shape == (128, 8)
    assert specs["attention2.attn.wkv_a"].shape == (128, 40)
    assert specs["kda3.kda.conv_v"].shape == (4, 64)
    assert specs["lm_head"].vocab_axis == 1


def test_published_file_leaves():
    m = _file()
    specs = REF.leaf_specs(m)
    assert [r[0] for r in REF.runs(m)] == [
        "kda0", "kda1", "attention3", "kda4", "attention7", "kda8",
        "attention11", "kda12", "attention15", "kda16", "attention19",
        "kda20", "attention23", "kda24", "attention26"]
    assert specs["kda4.kda.w_q"][:4:3] == ((2304, 4096), 3)
    assert specs["kda0.mlp.w_down"].shape == (9216, 2304)
    assert specs["kda0.kda.w_fb"].shape == (128, 4096)
    assert specs["kda24.kda.A_log"].shape == (32,)
    assert specs["kda24.moe.w_up"][:4:3] == ((16, 2304, 1024), 2)
    assert specs["attention26.moe.router"].shape == (2304, 256)
    assert specs["attention26.attn.wkv_b"].shape == (512, 32, 256)
    assert specs["attention3.moe.shared.w_up"].shape == (2304, 1024)
    total = sum((leaf.depth or 1) * math.prod(leaf.shape)
                for leaf in specs.values())
    assert total == 4956660608                    # 9.91 GB in bfloat16


def test_check_program_passes_the_file_and_names_a_changed_field(
        published):
    cfg = published
    assert cfg.experts_held == 16 and cfg.num_experts == 256
    assert cfg.num_layers == 27
    assert REF.check_program(cfg, _file()) == {}
    assert REF.check_program(_program(TINY_KIMI), TINY_KIMI) == {}
    for field, value in (("experts_held", 256), ("expert_offset", 16),
                         ("num_experts", 16), ("kda_heads", 16),
                         ("kda_conv", 3), ("shared_d_ff", 2048),
                         ("layer_types", ("kda",) * 27),
                         ("first_dense_layers", 0),
                         ("moe_score", "softmax"), ("use_rope", True)):
        wrong = REF.check_program(cfg.replace(**{field: value}), _file())
        key = "architecture" if field in ("moe_score", "use_rope") \
            else field
        assert key in wrong, (field, wrong)
    for key, value in (("mla_use_nope", False), ("moe_renormalize", False)):
        assert key in REF.check_program(cfg, {**_file(), key: value})
    lin = dict(_file()["linear_attn_config"], full_attn_layers=[4, 8])
    assert "linear_attn_config" in REF.check_program(
        cfg, {**_file(), "linear_attn_config": lin})


def test_counts_by_hand():
    m = _file()
    d, w = 2304, 4096
    kda = 3 * d * w + 2 * (d * 128 + 128 * w) + d * 32 + w * d
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    moe = d * 256 + 8 * 16 / 256 * 3 * d * 1024 + 3 * d * 1024
    params = 20 * kda + 7 * mla + 3 * d * 9216 + 26 * moe
    rec = 20 * (2 * 4 * 3 * w + 7 * 32 * 128 * 128 + 2 * 32 * 128)
    head = 2 * d * 163840
    # a prompt of 2 and 2 new tokens: prefill scores 1 + 2 keys (expanded)
    # in the 7 MLA layers, decode 3 latent positions
    prefill = (2 * params + rec) * 2 + 2 * 7 * 32 * (192 + 128) * 3 + head
    latent = 7 * 2 * 32 * (576 + 512) * 3
    decode = 2 * params + rec + latent + head
    assert REF.generate_flops(m, 2, 2) == round(prefill + decode)
    ops, nbytes = REF.decode_state_work(m)
    assert ops == 20 * (7 * 32 * 128 * 128 + 2 * 32 * 128)
    assert nbytes == 20 * 4 * (2 * 32 * 128 * 128 + 5 * 32 * 128 + 32)
    ops, nbytes = REF.decode_attention_work(m, 1000)
    assert ops == 7 * 2 * 32 * (576 + 512) * 1000
    assert nbytes == 7 * (2 * 576 * 1000 + 2 * 32 * (576 + 512))


def _traced_with(names, arch=REF):
    """A run whose trace holds a decode step with the given ops inside,
    and one op of the first name after the step."""
    ev = xtrace.Event
    step = ev("jit_serve_step", 1000.0, 2000.0)
    ops = [ev(f"%{n}.{i} = f32[4,32,128,1] custom-call(...), "
              'custom_call_target="tpu_custom_call"',
              1100.0 + 100 * i, 1150.0 + 100 * i) for i, n in enumerate(names)]
    ops.append(ev(f"%{names[0]}.9 = f32[4] custom-call(...)", 2100.0,
                  2200.0))
    trace = xtrace.Trace([xtrace.Device("/device:TPU:0", [step], ops)],
                         [ev("bench.window", 0.0, 3000.0)])
    call = types.SimpleNamespace(prompt_len=1000, new_tokens=3, hit=False,
                                 tokens=[0, 0, 0])
    return types.SimpleNamespace(
        trace=trace, trace_window=(0.0, 3000.0), peaks=peaks.peaks(
            "TPU v5 lite"), model=_file(), arch=arch, traced_calls=[call])


def test_kda_reader_reads_its_kernel_alone():
    """Only the ``kda_decode`` ops inside a decode step count, not the
    latent kernel's or the expert kernel's; the work is two decode steps
    of the traced call's state work."""
    reader = Spec(tiny.ROOT).reader("kda_decode_roofline.gen")
    run = _traced_with(["kda_decode", "latent_decode_attention",
                        "moe_decode"])
    ops, nbytes = REF.decode_state_work(run.model)
    least = 2 * max(ops / run.peaks["bf16_flops"],
                    nbytes / run.peaks["hbm_bytes_per_s"])
    assert reader.read(run) == pytest.approx(100 * least / 50e-9)
    assert reader.read(_traced_with(["latent_decode_attention"])) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    # the other architectures count no such work: nothing to read
    moon = Spec(tiny.ROOT).reference("deepseek_v3_lm")
    assert reader.read(_traced_with(["kda_decode"], arch=moon)) is None


def test_latent_reader_counts_the_mla_layers_alone():
    """The latent kernel's reader takes this module's work: the 7 MLA
    layers' latent rows, not 27 layers'."""
    reader = Spec(tiny.ROOT).reader("latent_attn_roofline.gen")
    run = _traced_with(["latent_decode_attention", "kda_decode"])
    nbytes = sum(REF.decode_attention_work(run.model, 1001 + i)[1]
                 for i in range(2))
    assert reader.read(run) == pytest.approx(
        100 * nbytes / run.peaks["hbm_bytes_per_s"] / 50e-9)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_tree(tmp_path_factory.mktemp("kimi"))
    tiny.add_config(root, TINY_KIMI, gap_limit=MEAN_LIMITS["logit_gap"])
    mix = dict(tiny.MIXES["tinyqa"], entry="generate_mean_gap",
               new_tokens=32)
    (root / "bench" / "traffic" / "tinyqa.mean_gap.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / f"{MEAN_CELL}.json").write_text(
        json.dumps(MEAN_LIMITS))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": MEAN_CELL,
                             "config": TINY_KIMI["name"],
                             "traffic": "tinyqa.mean_gap", "chips": 1,
                             "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_tiny_run_is_correct(root):
    result = tiny.run(root, CELL)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_token_altered_in_kimi_run(root, monkeypatch):
    from repro.serving import serve

    real = serve.BatchScheduler.step

    def step(self):
        done = real(self)
        for req in done:
            if len(req.generated) > 2:
                req.generated[2] = (req.generated[2] + 1) % \
                    TINY_KIMI["vocab_size"]
        return done

    monkeypatch.setattr(serve.BatchScheduler, "step", step)
    result = tiny.run(root, CELL)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]


def test_wrong_cache_hit_in_kimi_run(root, monkeypatch):
    """A hit whose clone differs from what its miss stored fails."""
    import numpy as np

    from repro.core import process
    from repro.core.datatypes import ArrayData

    real = process.Process._maybe_use_cache

    def use_cache(self):
        code = real(self)
        if code is not None and "tokens" in self.outputs:
            bad = np.asarray(self.outputs["tokens"].value)[::-1].copy()
            self.outputs["tokens"] = ArrayData(bad)
        return code

    monkeypatch.setattr(process.Process, "_maybe_use_cache", use_cache)
    result = tiny.run(root, "t.tiny-kimi.tinyzipf")
    assert not result["correct"]
    assert result["checks"]["hit_differs"]["value"] > 0


def test_cell_runs_docqa_with_both_gap_limits():
    """The cell's mix is moonlight's and granite-4.0-h's mean-gap docqa;
    its limits file sets both gaps; its own kernel's reader lists it, and
    the latent kernel's too."""
    doc = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in doc["workloads"] if c["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-linear-48b-a3b", "docqa.mean_gap", 1)
    spec = Spec(tiny.ROOT)
    assert set(spec.limits(CELL_NAME)) == {"logit_gap", "logit_gap_mean"}
    lists = {m["name"]: m.get("workloads", []) for m in doc["per_layer"]}
    assert lists["kda_decode_roofline.gen"] == [CELL_NAME]
    assert CELL_NAME in lists["latent_attn_roofline.gen"]
    assert CELL_NAME not in lists["decode_attn_roofline.gen"]


@pytest.mark.parametrize("seed", [3, 977, 2**31 + 7])
def test_control_fails_the_mean_where_the_program_passes(root, seed):
    """Through the harness: the program's run is correct with both gaps;
    the control in its place is not, by the mean gap."""
    import run as bench_run
    from entries.generate_mean_gap import control_checks

    _spec, run, _device = bench_run.run_cell(root, MEAN_CELL, seed, 2.0,
                                             False, False, 0.0)
    mean = run.checks["logit_gap_mean"]
    assert mean["limit"] == MEAN_LIMITS["logit_gap_mean"]
    assert bench_run.correct(run.checks), run.checks
    control = control_checks(run, seed)
    assert not bench_run.correct(control), control
    assert control["logit_gap_mean"]["value"] > mean["limit"]
