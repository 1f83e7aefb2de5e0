"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic
mix, metrics and limits are all found by name (``benchlib/spec.py``).
With ``--trace 0`` the last line of standard output is the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window's last seconds and from the program's own
spans and counters. The run fails, and prints no result, when JAX finds
no TPU or fewer chips than the cell asks for. The numbers compared to
decide ``correct`` are printed last on standard error and last in the
result line, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def _setup_paths_and_cache() -> None:
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # cache every program, also the many that compile in under a second
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no eviction: the directory is the checkout's own, and an evicting
    # cache refuses every write once one entry lacks its access-time file
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and (platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {platform} device(s)")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True,
             t_start: float | None = None):
    """One run of a cell; returns its record and the device it ran on.
    ``require_chip=False`` is for the CPU tests of the harness."""
    from benchlib.peaks import peaks
    from benchlib.spec import Spec

    spec = Spec(root)
    cell = spec.cell(workload)
    device = device_info(int(cell["chips"]), require_chip)
    device_peaks = peaks(device["kind"]) if require_chip else None
    mix = spec.traffic(cell["traffic"])
    workdir = Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        run = spec.entry(mix["entry"]).run(
            spec, cell, seed, seconds, trace, workdir,
            T_START if t_start is None else t_start)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    run.peaks = device_peaks
    device["memory_peak_bytes"] = run.memory_peak_bytes
    return spec, run, device


def correct(checks: dict) -> bool:
    """The run is correct when every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, require_chip: bool = True,
            t_start: float | None = None) -> dict:
    """One run; returns the result object."""
    spec, run, device = run_cell(root, workload, seed, seconds, trace,
                                 require_chip, t_start)
    metrics = {}
    for m in spec.metrics_for(workload, trace):
        value = spec.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct(run.checks),
              "attempted": len(run.calls), "failed": run.failed,
              "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        from benchlib import xtrace

        lo, hi = run.trace_window
        busy = [xtrace.busy_ns(d, lo, hi) for d in run.trace.devices]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = xtrace.breakdown(run.trace, lo, hi)
    result["checks"] = run.checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_paths_and_cache()
    try:
        result = execute(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
