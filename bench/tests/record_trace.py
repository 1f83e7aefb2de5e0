"""Records the small TPU trace that ``test_xtrace.py`` reads: one
``generate`` call of published qwen2-0.5b (a 32-token prompt, 4 new
tokens) through the engine, inside the benchmark's annotations.

    python3 bench/tests/record_trace.py    # on a machine with one TPU

Writes ``bench/tests/data/decode.xplane.pb``.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

PROMPT, NEW, SEED = 32, 4, 11


def main() -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    from repro.core.datatypes import ArrayData, Int, Str
    from repro.engine.launch import run_get_node
    from repro.engine.runner import Runner, set_default_runner
    from repro.provenance.store import configure_store
    from repro.serving.inference import generate

    tmp = Path(tempfile.mkdtemp())
    set_default_runner(Runner(store=configure_store(str(tmp / "p.db"))))
    rng = np.random.default_rng(SEED)

    def call():
        prompt = rng.integers(1, 151936, PROMPT).astype(np.int32)
        run_get_node(generate.process_class, arch=Str("qwen2-0.5b"),
                     prompt=ArrayData(prompt), max_new_tokens=Int(NEW),
                     seed=Int(SEED), eos_id=Int(-1))

    call()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call"):
            call()
    jax.profiler.stop_trace()
    found = sorted((tmp / "trace").rglob("*.xplane.pb"))
    (HERE / "data").mkdir(exist_ok=True)
    shutil.copy(found[-1], HERE / "data" / "decode.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
