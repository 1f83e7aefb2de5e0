"""Readings for the limit of the logit-gap check, program and control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: one run of the cell (window, checks, and
the reference at float32), then the control on the same requests, the
reference computed with every matrix-product operand in float8 e4m3.
Prints one JSON line per seed with the program's widest gap and the
control's (the gap of the token the control puts first), and whether
each is ``correct`` by the harness's own comparison: the control's gap
takes the program's place among the run's checks. The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def readings(workload: str, seed: int, seconds: float,
             require_chip: bool = True, root=bench_run.ROOT) -> dict:
    from entries.generate import gap

    _spec, run, device = bench_run.run_cell(root, workload, seed, seconds,
                                            False, require_chip,
                                            time.monotonic())
    tokens, rows, served = run.check_batch
    exact = run.arch.logits_at(run.model, seed, tokens, rows)
    low = run.arch.logits_at(run.model, seed, tokens, rows, "fp8")
    control_checks = {**run.checks, "logit_gap": {
        **run.checks["logit_gap"], "value": gap(exact, low.argmax(axis=-1))}}
    return {"workload": workload, "seed": seed, "device": device["kind"],
            "calls": len(run.calls), "served_compared": len(served),
            "program_gap": gap(exact, served),
            "control_gap": control_checks["logit_gap"]["value"],
            "program_correct": bench_run.correct(run.checks),
            "control_correct": bench_run.correct(control_checks),
            "checks": run.checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args(argv)
    bench_run._setup_paths_and_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
