"""The DeepSeek-V3 architecture module (``refs/deepseek_v3_lm.py``) and the
cell of Moonlight-16B-A3B: its leaves are the program's tree leaf for
leaf, its program check, its counts by hand, its latent-kernel reader, and
a whole tiny run through the harness that an altered token makes not
correct, and the mean-gap check that tells the program from the control."""

from __future__ import annotations

import json
import math
import types

import jax
import numpy as np
import pytest

import tiny
from benchlib import peaks, weights, xtrace
from benchlib.spec import Spec

REF = Spec(tiny.ROOT).reference("deepseek_v3_lm")
#: ``reduced_config("moonlight-16b-a3b")`` as a configuration file states
#: it: chip 1 of 2, holding experts 4-7 of 8
TINY_MOON = {
    "name": "tiny-moon", "reference": "deepseek_v3_lm",
    "num_hidden_layers": 3, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 256, "vocab_size": 512,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_a_layernorm_eps": 1e-6, "first_k_dense_replace": 1,
    "moe_intermediate_size": 64, "n_shared_experts": 2, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "q_lora_rank": None, "moe_layer_freq": 1, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_theta": 50000.0, "rms_norm_eps": 1e-05,
    "published": {"n_routed_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 1, "first_expert": 4},
    "program": {"arch": "moonlight-16b-a3b", "reduced": True,
                "dtype": "bfloat16", "param_dtype": "float32",
                "kv_cache_dtype": "bfloat16", "decode_impl": "pallas"},
}
CELL = "t.tiny-moon.tinyqa"
#: the tiny cell through ``entries/generate_mean_gap.py``. At this size the
#: program's mean gap read 0.0002-0.023 over seeds 3, 5, 977 and 2**31 + 7,
#: the control's 0.14-0.17; the widest gap reached 1.34 and 2.04 (CPU runs;
#: the compared calls depend on the window)
MEAN_CELL = "t.tiny-moon.tinyqa_mean_gap"
MEAN_LIMITS = {"logit_gap": 2.5, "logit_gap_mean": 0.06}


def _file():
    return json.loads((tiny.BENCH / "configs" /
                       "moonlight-16b-a3b.json").read_text())


def _program(m):
    from entries.generate import program_config

    return program_config(m, REF)


@pytest.fixture
def published(monkeypatch):
    """The program's config for the published file; its overrides set the
    registered config, which is restored after the test."""
    import repro.configs.moonlight_16b_a3b as mod

    monkeypatch.setattr(mod, "CONFIG", mod.CONFIG)
    return _program(_file())


def test_leaves_are_the_program_tree_leaf_for_leaf():
    from repro.models.registry import build

    cfg = _program(TINY_MOON)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    specs = REF.leaf_specs(TINY_MOON)
    out = weights.overwrite(params, specs, 11)   # raises on any mismatch
    names = {weights.path_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(out)[0]}
    assert names == set(specs)
    assert specs["dense_layers.attn.wq"].depth == 1
    assert specs["layers.moe.w_gate"].depth == 2
    assert specs["layers.moe.w_gate"].shape == (4, 128, 64)
    assert specs["layers.moe.router"].shape == (128, 8)
    assert specs["lm_head"].vocab_axis == 1


def test_published_file_leaves():
    m = _file()
    specs = REF.leaf_specs(m)
    assert specs["dense_layers.mlp.w_gate"][:4:3] == ((2048, 11264), 1)
    assert specs["layers.attn.wkv_a"][:4:3] == ((2048, 576), 26)
    assert specs["layers.attn.wkv_b"].shape == (512, 16, 256)
    assert specs["layers.moe.w_down"].shape == (8, 1408, 2048)
    assert specs["layers.moe.router"].shape == (2048, 64)
    assert specs["layers.moe.shared.w_up"].shape == (2048, 2816)
    total = sum((leaf.depth or 1) * math.prod(leaf.shape)
                for leaf in specs.values())
    assert total == 3364615296


def test_check_program_passes_the_file_and_names_a_changed_field(
        published):
    cfg = published
    assert cfg.experts_held == 8 and cfg.num_experts == 64
    assert REF.check_program(cfg, _file()) == {}
    assert REF.check_program(_program(TINY_MOON), TINY_MOON) == {}
    for field, value in (("experts_held", 64), ("expert_offset", 8),
                         ("num_experts", 8), ("moe_routed_scale", 1.0),
                         ("latent_norm_eps", 1e-5), ("shared_d_ff", 1408),
                         ("moe_impl", "gshard"), ("first_dense_layers", 0)):
        wrong = REF.check_program(cfg.replace(**{field: value}), _file())
        key = "architecture" if field == "moe_impl" else field
        assert key in wrong, (field, wrong)
    for key, value in (("q_lora_rank", 1536), ("norm_topk_prob", False)):
        assert key in REF.check_program(cfg, {**_file(), key: value})


def test_counts_by_hand():
    m = _file()
    d, h = 2048, 16
    attn = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    moe = d * 64 + 3 * d * 2816 + 0.75 * 3 * d * 1408
    params = 27 * attn + 3 * d * 11264 + 26 * moe
    head = 2 * d * 163840
    # a prompt of 2 and 2 new tokens: prefill scores 1 + 2 keys with
    # expanded heads (192 + 128), decode 3 positions of latent rows
    prefill = 2 * params * 2 + 2 * 27 * h * 320 * 3 + head
    decode = 2 * params + 2 * 27 * h * (576 + 512) * 3 + head
    assert REF.generate_flops(m, 2, 2) == round(prefill + decode)
    ops, nbytes = REF.decode_attention_work(m, 1000)
    assert ops == 27 * 2 * 16 * (576 + 512) * 1000
    assert nbytes == 27 * (1152 * 1000 + 2 * 16 * (576 + 512))


def _traced_with(names):
    """A run whose trace holds decode steps with the given ops inside."""
    ev = xtrace.Event
    step = ev("jit_serve_step", 1000.0, 2000.0)
    ops = [ev(f"%{n}.{i} = bf16[4,16,512] custom-call(...), "
              'custom_call_target="tpu_custom_call"',
              1100.0 + 100 * i, 1150.0 + 100 * i) for i, n in enumerate(names)]
    trace = xtrace.Trace([xtrace.Device("/device:TPU:0", [step], ops)],
                         [ev("bench.window", 0.0, 3000.0)])
    call = types.SimpleNamespace(prompt_len=1000, new_tokens=2, hit=False,
                                 tokens=[0, 0])
    return types.SimpleNamespace(
        trace=trace, trace_window=(0.0, 3000.0), peaks=peaks.peaks(
            "TPU v5 lite"), model=_file(), arch=REF, traced_calls=[call])


def test_latent_reader_reads_its_kernel_alone():
    reader = Spec(tiny.ROOT).reader("latent_attn_roofline.gen")
    run = _traced_with(["latent_decode_attention", "decode_attention",
                        "ragged-dot-metadata"])
    ops, nbytes = REF.decode_attention_work(run.model, 1001)
    least = max(ops / run.peaks["bf16_flops"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    assert reader.read(run) == pytest.approx(100 * least / 50e-9)
    assert reader.read(_traced_with(["decode_attention"])) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_tree(tmp_path_factory.mktemp("moon"))
    tiny.add_config(root, TINY_MOON)
    mix = dict(tiny.MIXES["tinyqa"], entry="generate_mean_gap")
    (root / "bench" / "traffic" / "tinyqa.mean_gap.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / f"{MEAN_CELL}.json").write_text(
        json.dumps(MEAN_LIMITS))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": MEAN_CELL, "config": TINY_MOON["name"],
                             "traffic": "tinyqa.mean_gap", "chips": 1,
                             "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_tiny_run_is_correct(root):
    result = tiny.run(root, CELL)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_token_altered_in_latent_run(root, monkeypatch):
    from repro.serving import serve

    real = serve.BatchScheduler.step

    def step(self):
        done = real(self)
        for req in done:
            if len(req.generated) > 2:
                req.generated[2] = (req.generated[2] + 1) % \
                    TINY_MOON["vocab_size"]
        return done

    monkeypatch.setattr(serve.BatchScheduler, "step", step)
    result = tiny.run(root, CELL)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]


def test_mean_gap_mix_is_docqa():
    """The cell's mix sends docqa's requests; only its entry differs."""
    doc = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in doc["workloads"]
                if c["name"] == "gen.moonlight-16b-a3b.docqa")
    spec = Spec(tiny.ROOT)
    mix = spec.traffic(cell["traffic"])
    assert mix.pop("entry") == "generate_mean_gap"
    docqa = spec.traffic("docqa")
    assert docqa.pop("entry") == "generate"
    assert mix == docqa
    assert set(spec.limits(cell["name"])) == {"logit_gap", "logit_gap_mean"}


def test_gaps_by_hand():
    from entries.generate_mean_gap import gaps

    logits = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, -1.0]])
    assert gaps(logits, np.array([2, 0])).tolist() == [1.0, 0.0]
    assert gaps(logits, np.array([1, 2])).tolist() == [0.0, 1.5]


@pytest.mark.parametrize("seed", [3, 977, 2**31 + 7])
def test_control_fails_the_mean_where_the_program_passes(root, seed):
    """Through the harness: the program's run is correct with both gaps;
    the control in its place is not, by the mean gap, whatever its widest
    gap reads."""
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
    assert control["logit_gap_mean"]["value"] >= 5 * mean["value"]
