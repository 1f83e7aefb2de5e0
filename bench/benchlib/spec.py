"""Finds everything a cell needs by name, each in a file of its own:

* ``BENCHMARK.json`` at the root: cells, metrics, bounds;
* ``bench/configs/<config>.json``: the model configuration as it runs;
* ``bench/refs/<reference>.py``: its architecture module, named by the
  config (below);
* ``bench/traffic/<mix>.json``: a traffic mix for ``benchlib.traffic``;
* ``bench/entries/<entry>.py``: the code that runs the entry point a mix names;
* ``bench/metrics/<metric>.py``: the reader of one metric;
* ``bench/limits/<cell>.json``: the limits of the cell's correctness check.

Adding a configuration, mix, metric or cell is adding files; no file that
exists has to change. The architecture module is the one place that knows
the model's architecture; ``m`` is the configuration file's dictionary:

* ``logits_at(m, seed, tokens, rows, precision="f32")``: the plain float32
  reference's logits at (sequence, position) ``rows`` of ``tokens``, from
  the seeded weights; ``precision="fp8"`` is the control;
* ``leaf_specs(m)``: every weight leaf of the program's tree by its dotted
  path, as a ``benchlib.weights.Leaf`` (per-layer shape, standard
  deviation, mean, the depth it is stacked over, its vocabulary axis);
  ``benchlib.weights`` draws and writes them;
* ``check_program(cfg, m)``: the fields of the program's ``ModelConfig``
  that differ from the file, as name -> (program's, file's); the run stops
  before any call where there is one;
* ``generate_flops(m, prompt, new_tokens)``: model operations of one
  request, prefill and decode;
* ``decode_attention_work(m, context)``: (operations, bytes) of decode
  attention for one sequence over ``context`` positions, summed over every
  layer that holds attention.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads((self.bench / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.bench / "limits" / f"{cell}.json").read_text())

    def reference(self, name: str):
        return _module(self.bench / "refs" / f"{name}.py", f"bench_ref_{name}")

    def entry(self, name: str):
        return _module(self.bench / "entries" / f"{name}.py",
                       f"bench_entry_{name}")

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or its per-layer ones."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        return _module(self.bench / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}")
