"""Configurations, mixes and metrics are found by name: a throwaway mix,
a throwaway metric and a cell that uses them are added as new files, and
a run reports the metric with no edit to any file that was there."""

from __future__ import annotations

import json

import tiny


def test_added_files_are_found_by_name(tmp_path):
    root = tiny.make_tree(tmp_path)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "traffic" / "throwaway.json").write_text(json.dumps(
        {"entry": "generate", "prompt_lengths": {"12": 1}, "new_tokens": 3,
         "eos_id": -1, "pool": 2, "zipf_s": 1.0, "order_seed": 1,
         "check_requests": 2}))
    (bench / "metrics" / "throwaway_hits.py").write_text(
        "def read(run):\n    return run.counters['cache.hits']\n")
    (bench / "limits" / "t.tiny.throwaway.json").write_text(
        json.dumps({"logit_gap": 0.5}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "t.tiny.throwaway", "config": "tiny",
                             "traffic": "throwaway", "chips": 1,
                             "why": "test"})
    doc["end_to_end"].append({"name": "throwaway_hits", "unit": "hits",
                              "better": "higher", "bound": 0.1,
                              "source": "host_clock",
                              "workloads": ["t.tiny.throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert all(p.read_bytes() == b for p, b in before.items())

    result = tiny.run(root, "t.tiny.throwaway", seconds=1.0)
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    assert set(metrics) == {"setup_s", "throwaway_hits"}
    # two prompts in the pool: all but the first call of each is a hit
    assert metrics["throwaway_hits"]["value"] >= result["attempted"] - 2
