"""Continuous-batching LM serving under a seeded Zipfian prompt workload.

Two sections, one engine (reduced ``aiida-demo-110m`` decoding through the
Pallas flash-decode kernel, interpreted on CPU):

* **scheduler** — drive the :class:`~repro.serving.serve.BatchScheduler`
  directly with all-distinct prompts: raw continuous-batching throughput
  (tokens/s) with slot eviction + FIFO re-admission mid-flight;
* **cached serving** — replay a Zipf-distributed request stream through
  the :func:`repro.serving.inference.generate` calcfunction against one
  provenance store with caching enabled. Repeated prompts must resolve on
  the content-addressed fast path: the ``serving.decode_steps`` counter
  does not move for a hit, which is how hits are detected and asserted.

``--smoke`` shrinks everything for CI and exits non-zero unless (a) a
repeated prompt is served with zero decode steps and (b) scheduler
tokens/s > 0. A full run writes ``BENCH_serve.json``.

    PYTHONPATH=src python -m benchmarks.serve_bench --requests 80
    PYTHONPATH=src python -m benchmarks.serve_bench --smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

import numpy as np

ARCH = "aiida-demo-110m"


def zipf_indices(rng: np.random.Generator, n_requests: int, pool: int,
                 a: float) -> np.ndarray:
    """Zipf-by-rank over a finite pool: P(rank r) ~ 1/r^a, r = 1..pool."""
    w = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** a
    return rng.choice(pool, size=n_requests, p=w / w.sum())


def make_prompt_pool(rng: np.random.Generator, pool: int, prompt_len: int,
                     vocab: int) -> list[list[int]]:
    return [rng.integers(1, vocab, prompt_len).tolist() for _ in range(pool)]


def bench_scheduler(seed: int, n_requests: int, prompt_len: int,
                    new_tokens: int, batch: int) -> dict:
    """Raw continuous-batching throughput: all-distinct prompts, more
    requests than slots, so eviction/re-admission happens mid-flight."""
    from repro.observability.metrics import get_registry
    from repro.serving.inference import get_engine, reset_engines

    reset_engines()
    eng = get_engine(ARCH, seed, reduced=True,
                     need_len=prompt_len + new_tokens, batch_size=batch)
    rng = np.random.default_rng(seed)
    prompts = make_prompt_pool(rng, n_requests, prompt_len,
                               eng.cfg.vocab_size)
    # warm the compile caches (prefill at this length + the decode step)
    eng.generate_many([prompts[0]], 2)

    steps0 = get_registry().counter("serving.decode_steps").value
    t0 = time.perf_counter()
    reqs = eng.generate_many(prompts, new_tokens)
    dt = time.perf_counter() - t0
    steps = get_registry().counter("serving.decode_steps").value - steps0

    toks = sum(len(r.generated) for r in reqs)
    assert all(r.done for r in reqs)
    return {
        "requests": n_requests,
        "batch_size": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_steps": int(steps),
        "tokens_generated": int(toks),
        "wall_seconds": round(dt, 4),
        "tokens_per_s": round(toks / dt, 2),
    }


def bench_cached_serving(seed: int, n_requests: int, pool: int,
                         prompt_len: int, new_tokens: int,
                         zipf_a: float) -> dict:
    """Zipfian request stream through the generate() calcfunction with the
    content-addressed cache on; hits are calls that ran zero decode steps."""
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.engine.runner import Runner, set_default_runner
    from repro.observability.metrics import get_registry
    from repro.provenance.store import configure_store
    from repro.serving.inference import generate, reset_engines

    store = configure_store(":memory:")
    runner = Runner(store=store)
    set_default_runner(runner)
    reset_engines()

    rng = np.random.default_rng(seed)
    from repro.configs import reduced_config
    vocab = reduced_config(ARCH).vocab_size
    prompts = make_prompt_pool(rng, pool, prompt_len, vocab)
    stream = zipf_indices(rng, n_requests, pool, zipf_a)

    decode_steps = get_registry().counter("serving.decode_steps")
    hits = 0
    toks = 0
    results: dict[int, list[int]] = {}
    t0 = time.perf_counter()
    with enable_caching():
        for idx in stream:
            before = decode_steps.value
            out = generate(Str(ARCH), ArrayData(np.asarray(prompts[idx],
                                                           np.int32)),
                           Int(new_tokens), Int(seed), Int(-1), Bool(True))
            got = [int(t) for t in np.asarray(out["tokens"].value)]
            if decode_steps.value == before:
                hits += 1
                assert results[int(idx)] == got, \
                    f"cache hit for prompt {idx} returned different tokens"
            else:
                results.setdefault(int(idx), got)
            toks += len(got)
    dt = time.perf_counter() - t0

    distinct = len(set(int(i) for i in stream))
    return {
        "requests": n_requests,
        "prompt_pool": pool,
        "distinct_prompts_drawn": distinct,
        "zipf_a": zipf_a,
        "new_tokens": new_tokens,
        "cache_hits": hits,
        "cache_hit_rate": round(hits / n_requests, 4),
        "expected_hit_rate": round(1.0 - distinct / n_requests, 4),
        "tokens_served": int(toks),
        "wall_seconds": round(dt, 4),
        "tokens_per_s": round(toks / dt, 2),
    }


def assert_hit_fast_path(seed: int) -> None:
    """The --smoke acceptance check: the SECOND occurrence of a prompt runs
    zero decode steps and returns identical tokens."""
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.engine.runner import Runner, set_default_runner
    from repro.observability.metrics import get_registry
    from repro.provenance.store import configure_store
    from repro.serving.inference import generate, reset_engines

    store = configure_store(":memory:")
    set_default_runner(Runner(store=store))
    reset_engines()

    prompt = ArrayData(np.asarray([7, 11, 13, 17, 19, 23], np.int32))
    decode_steps = get_registry().counter("serving.decode_steps")
    with enable_caching():
        cold = generate(Str(ARCH), prompt, Int(5), Int(seed), Int(-1),
                        Bool(True))
        before = decode_steps.value
        hot = generate(Str(ARCH), prompt, Int(5), Int(seed), Int(-1),
                       Bool(True))
    ran = decode_steps.value - before
    same = np.array_equal(np.asarray(cold["tokens"].value),
                          np.asarray(hot["tokens"].value))
    print(f"repeat-prompt fast path: decode steps on 2nd call = {ran}, "
          f"tokens identical = {same}")
    if ran != 0 or not same:
        print("FAIL: cache-hit fast path did not fire", file=sys.stderr)
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + hard asserts for CI; no json output")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=80)
    ap.add_argument("--pool", type=int, default=12,
                    help="distinct prompts in the Zipf pool")
    ap.add_argument("--zipf-a", type=float, default=1.3)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    if args.smoke:
        args.requests, args.pool = 10, 3
        args.new_tokens, args.prompt_len = 4, 6

    sched = bench_scheduler(args.seed, max(args.requests // 4, args.batch + 2),
                            args.prompt_len, args.new_tokens, args.batch)
    print(f"scheduler: {sched['requests']} reqs through "
          f"{sched['batch_size']} slots -> {sched['tokens_generated']} tok "
          f"in {sched['wall_seconds']}s ({sched['tokens_per_s']} tok/s, "
          f"{sched['decode_steps']} decode steps)")

    served = bench_cached_serving(args.seed, args.requests, args.pool,
                                  args.prompt_len, args.new_tokens,
                                  args.zipf_a)
    print(f"cached serving: {served['requests']} reqs over "
          f"{served['prompt_pool']}-prompt Zipf(a={served['zipf_a']}) pool "
          f"-> hit rate {served['cache_hit_rate']} "
          f"(expected {served['expected_hit_rate']}), "
          f"{served['tokens_per_s']} tok/s")

    if args.smoke:
        assert_hit_fast_path(args.seed)
        ok = (sched["tokens_per_s"] > 0
              and served["cache_hit_rate"] == served["expected_hit_rate"])
        print("smoke:", "PASS" if ok else "FAIL")
        if not ok:
            sys.exit(1)
        return

    payload = {
        "bench": "serve",
        "arch": ARCH,
        "seed": args.seed,
        "scheduler": sched,
        "cached_serving": served,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
