"""A throwaway benchmark tree at the program's CPU size, for the tests:
the real ``bench/`` code and data, plus a tiny configuration, tiny mixes,
their cells and limits, all added as new files by name."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: reduced_config("qwen2-0.5b"), the size the program's CPU tests use
TINY = {
    "name": "tiny", "reference": "dense_lm",
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 512, "qkv_bias": True, "tie_word_embeddings": True,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "program": {"arch": "qwen2-0.5b", "reduced": True, "dtype": "bfloat16",
                "param_dtype": "float32", "kv_cache_dtype": "bfloat16",
                "decode_impl": "pallas"},
}
MIXES = {
    "tinyzipf": {"entry": "generate", "prompt_lengths": {"8": 1, "16": 1},
                 "new_tokens": 4, "eos_id": -1, "pool": 12, "zipf_s": 1.1,
                 "order_seed": 0, "check_requests": 4},
    "tinyqa": {"entry": "generate", "prompt_lengths": {"24": 2, "40": 1},
               "new_tokens": 8, "eos_id": -1, "pool": None,
               "check_requests": 8},
}


def make_tree(tmp: Path, gap_limit: float = 0.5) -> Path:
    """A checkout-like root under ``tmp`` with the tiny cells added."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for mix, body in MIXES.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(body))
    add_config(root, TINY, gap_limit)
    return root


def add_config(root: Path, config: dict, gap_limit: float = 0.5) -> list[str]:
    """Adds ``config`` to a tree that ``make_tree`` made, and a cell
    ``t.<name>.<mix>`` of it under each tiny mix, as new files and new
    entries of ``BENCHMARK.json``; returns the cells' names."""
    name = config["name"]
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(config))
    cells = []
    for mix in MIXES:
        cell = f"t.{name}.{mix}"
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"logit_gap": gap_limit}))
        cells.append({"name": cell, "config": name, "traffic": mix,
                      "chips": 1, "why": "CPU test"})
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"] += cells
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c["name"] for c in cells]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return [c["name"] for c in cells]


def run(root: Path, cell: str, seed: int = 5, seconds: float = 2.0,
        trace: bool = False) -> dict:
    import run as bench_run

    return bench_run.execute(root, cell, seed, seconds, trace,
                             require_chip=False, t_start=0.0)
