"""The GraniteMoeHybrid architecture module
(``refs/granite_moe_hybrid_lm.py``) and the cell of Granite-4.0-H-Small:
its leaves are the program's tree leaf for leaf, its program check, its
counts by hand, its Mamba-kernel reader, and a whole tiny run through the
harness that an altered token makes not correct, and the mean-gap check
that tells the program from the control."""

from __future__ import annotations

import json
import math
import types

import jax
import pytest

import tiny
from benchlib import peaks, weights, xtrace
from benchlib.spec import Spec

REF = Spec(tiny.ROOT).reference("granite_moe_hybrid_lm")
CELL_NAME = "gen.granite-4.0-h-small.docqa"
#: ``reduced_config("granite-4.0-h-small")`` as a configuration file
#: states it: chip 0 of 2, holding experts 0-3 of 8
TINY_HYBRID = {
    "name": "tiny-hybrid", "reference": "granite_moe_hybrid_lm",
    "model_type": "granitemoehybrid", "num_hidden_layers": 4,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 512, "intermediate_size": 64,
    "shared_intermediate_size": 128, "num_local_experts": 4,
    "num_experts_per_tok": 3, "tie_word_embeddings": True,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
    "logits_scaling": 16.0,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 0.5, "hidden_act": "silu",
    "position_embedding_type": "nope", "attention_bias": False,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "normalization_function": "rmsnorm",
    "published": {"num_local_experts": 8},
    "expert_parallel": {"chips": 2, "chip": 0, "first_expert": 0},
    "program": {"arch": "granite-4.0-h-small", "reduced": True,
                "dtype": "bfloat16", "param_dtype": "float32",
                "kv_cache_dtype": "bfloat16", "decode_impl": "pallas"},
}
CELL = "t.tiny-hybrid.tinyqa"
#: the tiny cells' limits. At this size the logits are about 0.005 (a tied
#: head at the embedding's scale, divided by 16), and bfloat16 logits tie
#: at about 6e-5, so both gaps swing with which requests the window
#: finished. Read over every window (each count of finished calls, from
#: one to about 150, CPU runs) for seeds 3, 5, 977 and 2**31 + 7: the
#: program's widest gap reached 0.0032 with tinyqa's 8 new tokens, and the
#: control's widest fell to 0.0007, so the widest is a backstop only, below
#: the gap of a token altered to its neighbour id. With 32 new tokens (256
#: compared) the program's mean read at most 0.0000167 and the control's
#: at least 0.0000999, for every window; with 8 (64 compared) they
#: overlapped, so the mean-gap cell's mix asks for 32
MEAN_LIMITS = {"logit_gap": 0.005, "logit_gap_mean": 0.00006}
MEAN_CELL = "t.tiny-hybrid.tinyqa_mean_gap"


def _file():
    return json.loads((tiny.BENCH / "configs" /
                       "granite-4.0-h-small.json").read_text())


def _program(m):
    from entries.generate import program_config

    return program_config(m, REF)


@pytest.fixture
def published(monkeypatch):
    """The program's config for the published file; its overrides set the
    registered config, which is restored after the test."""
    import repro.configs.granite_4_0_h_small as mod

    monkeypatch.setattr(mod, "CONFIG", mod.CONFIG)
    return _program(_file())


def test_leaves_are_the_program_tree_leaf_for_leaf():
    from repro.models.registry import build

    cfg = _program(TINY_HYBRID)
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    specs = REF.leaf_specs(TINY_HYBRID)
    out = weights.overwrite(params, specs, 11)   # raises on any mismatch
    names = {weights.path_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(out)[0]}
    assert names == set(specs)
    assert specs["mamba0.ssm.w_x"].depth == 2
    assert specs["attention2.attn.wq"].depth == 1
    assert specs["mamba3.moe.w_gate"].shape == (4, 128, 64)
    assert specs["mamba3.moe.router"].shape == (128, 8)
    assert specs["embedding"].vocab_axis == 0
    assert not any(name.endswith("router_bias") for name in specs)


def test_published_file_leaves():
    m = _file()
    specs = REF.leaf_specs(m)
    assert [r[0] for r in REF.runs(m)] == [
        "mamba0", "attention5", "mamba6", "attention15", "mamba16"]
    assert specs["mamba6.ssm.w_x"][:4:3] == ((4096, 8192), 9)
    assert specs["mamba16.ssm.w_B"].shape == (4096, 128)
    assert specs["mamba0.ssm.conv_w"].shape == (4, 8448)
    assert specs["mamba0.ssm.out_proj"].shape == (8192, 4096)
    assert specs["attention15.attn.wk"].shape == (4096, 8, 128)
    assert specs["mamba0.moe.w_down"].shape == (9, 768, 4096)
    assert specs["mamba0.moe.router"].shape == (4096, 72)
    assert specs["attention5.moe.shared.w_up"].shape == (4096, 1536)
    total = sum((leaf.depth or 1) * math.prod(leaf.shape)
                for leaf in specs.values())
    assert total == 4418340096                    # 8.84 GB in bfloat16


def test_check_program_passes_the_file_and_names_a_changed_field(
        published):
    cfg = published
    assert cfg.experts_held == 9 and cfg.num_experts == 72
    assert cfg.num_layers == 20
    assert REF.check_program(cfg, _file()) == {}
    assert REF.check_program(_program(TINY_HYBRID), TINY_HYBRID) == {}
    for field, value in (("experts_held", 72), ("expert_offset", 9),
                         ("num_experts", 9), ("ssm_state", 64),
                         ("ssm_chunk", 128), ("shared_d_ff", 768),
                         ("logits_scaling", 8.0),
                         ("layer_types", ("mamba",) * 40),
                         ("moe_score", "sigmoid"), ("use_rope", True)):
        wrong = REF.check_program(cfg.replace(**{field: value}), _file())
        key = "architecture" if field in ("moe_score", "use_rope") \
            else field
        assert key in wrong, (field, wrong)
    for key, value in (("position_embedding_type", "rope"),
                       ("mamba_conv_bias", False)):
        assert key in REF.check_program(cfg, {**_file(), key: value})


def test_counts_by_hand():
    m = _file()
    d = 4096
    ssm = d * (2 * 8192 + 2 * 128 + 128) + 8192 * d
    attn = 2 * d * 32 * 128 + 2 * d * 8 * 128
    moe = d * 72 + 10 * 9 / 72 * 3 * d * 768 + 3 * d * 1536
    params = 18 * ssm + 2 * attn + 20 * moe
    rec = 18 * (2 * 4 * 8448 + 5 * 128 * 64 * 128 + 2 * 8192)
    head = 2 * d * 100352
    # a prompt of 2 and 2 new tokens: prefill scores 1 + 2 keys in the 2
    # attention layers, decode 3 positions
    prefill = (2 * params + rec) * 2 + 4 * 2 * 32 * 128 * 3 + head
    decode = 2 * params + rec + 4 * 2 * 32 * 128 * 3 + head
    assert REF.generate_flops(m, 2, 2) == round(prefill + decode)
    ops, nbytes = REF.decode_state_work(m)
    assert ops == 18 * (5 * 128 * 64 * 128 + 2 * 8192)
    assert nbytes == 18 * 4 * (2 * 128 * 64 * 128 + 2 * 8192 + 2 * 128
                               + 2 * 128)
    ops, nbytes = REF.decode_attention_work(m, 1000)
    assert ops == 2 * 4 * 32 * 128 * 1000
    assert nbytes == 2 * (4 * 8 * 128 * 1000 + 4 * 32 * 128)


def _traced_with(names, model=None, arch=REF):
    """A run whose trace holds decode steps with the given ops inside,
    and one op of the first name after the last step."""
    ev = xtrace.Event
    step = ev("jit_serve_step", 1000.0, 2000.0)
    ops = [ev(f"%{n}.{i} = f32[4,128,8192] custom-call(...), "
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
            "TPU v5 lite"), model=model or _file(), arch=arch,
        traced_calls=[call])


def test_ssm_reader_reads_its_kernel_alone():
    """Only the ``ssm_decode`` ops inside a decode step count, not the
    attention kernel's or the grouped matmul's; the work is two decode
    steps of the traced call's state work."""
    reader = Spec(tiny.ROOT).reader("ssm_decode_roofline.gen")
    run = _traced_with(["ssm_decode", "decode_attention",
                        "ragged-dot-metadata"])
    ops, nbytes = REF.decode_state_work(run.model)
    least = 2 * max(ops / run.peaks["bf16_flops"],
                    nbytes / run.peaks["hbm_bytes_per_s"])
    assert reader.read(run) == pytest.approx(100 * least / 50e-9)
    assert reader.read(_traced_with(["decode_attention"])) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    # the other architectures count no state work: nothing to read
    moon = Spec(tiny.ROOT).reference("deepseek_v3_lm")
    assert reader.read(_traced_with(["ssm_decode"], arch=moon)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_tree(tmp_path_factory.mktemp("hybrid"))
    tiny.add_config(root, TINY_HYBRID, gap_limit=MEAN_LIMITS["logit_gap"])
    mix = dict(tiny.MIXES["tinyqa"], entry="generate_mean_gap",
               new_tokens=32)
    (root / "bench" / "traffic" / "tinyqa.mean_gap.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / f"{MEAN_CELL}.json").write_text(
        json.dumps(MEAN_LIMITS))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": MEAN_CELL,
                             "config": TINY_HYBRID["name"],
                             "traffic": "tinyqa.mean_gap", "chips": 1,
                             "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_tiny_run_is_correct(root):
    result = tiny.run(root, CELL)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_token_altered_in_hybrid_run(root, monkeypatch):
    from repro.serving import serve

    real = serve.BatchScheduler.step

    def step(self):
        done = real(self)
        for req in done:
            if len(req.generated) > 2:
                req.generated[2] = (req.generated[2] + 1) % \
                    TINY_HYBRID["vocab_size"]
        return done

    monkeypatch.setattr(serve.BatchScheduler, "step", step)
    result = tiny.run(root, CELL)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]


def test_cell_runs_docqa_with_both_gap_limits():
    """The cell's mix is moonlight's mean-gap docqa; its limits file sets
    both gaps."""
    doc = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in doc["workloads"] if c["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-small", "docqa.mean_gap", 1)
    spec = Spec(tiny.ROOT)
    assert set(spec.limits(CELL_NAME)) == {"logit_gap", "logit_gap_mean"}
    ssm = next(m for m in doc["per_layer"]
               if m["name"] == "ssm_decode_roofline.gen")
    assert ssm["workloads"] == [CELL_NAME]


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
