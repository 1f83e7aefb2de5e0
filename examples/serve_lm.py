"""Continuous-batching LM serving on a small model.

    PYTHONPATH=src python examples/serve_lm.py --batch 4 --requests 10

Runs on whatever devices JAX finds (a TPU where one is attached; set
``JAX_PLATFORMS=cpu`` to force the host). A
:class:`~repro.serving.serve.BatchScheduler`
drives prefill + per-slot-position decode: requests of different prompt
lengths and budgets are co-batched, evicted on completion, and replaced
from the FIFO queue mid-flight. ``--decode-impl pallas`` routes the
decode inner product through the flash-decode kernel (interpreted off
TPU); ``--int8-kv`` quantises the KV cache.
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import enable_compile_cache, get_config
from repro.models.registry import build
from repro.serving.serve import BatchScheduler, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (micro-batch size)")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--decode-impl", default="pallas",
                    choices=["direct", "pallas"])
    ap.add_argument("--int8-kv", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    devices = jax.devices()
    print(f"devices: {len(devices)}x {devices[0].device_kind}")

    cfg = get_config("aiida-demo-110m").replace(
        num_layers=4, d_model=256, num_heads=4, num_kv_heads=2, d_ff=704,
        vocab_size=8192, decode_impl=args.decode_impl,
        kv_cache_dtype="int8" if args.int8_kv else "bfloat16")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    max_len = args.max_prompt_len + args.new_tokens + 1
    sched = BatchScheduler(bundle, params, batch_size=args.batch,
                           max_len=max_len)

    # mixed-length prompts from a small length set (each distinct prompt
    # length compiles its own prefill; decode is one shared program)
    rng = np.random.default_rng(0)
    lengths = [args.max_prompt_len, args.max_prompt_len // 2]
    t0 = time.time()
    for rid in range(args.requests):
        n = lengths[rid % len(lengths)]
        sched.submit(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
            max_new_tokens=args.new_tokens - (rid % 3) * 4))
    finished = sched.run()
    dt = time.time() - t0

    toks = sum(len(r.generated) for r in finished)
    print(f"served {len(finished)} requests through {args.batch} slots in "
          f"{dt:.2f}s ({toks} tokens, {toks/dt:.0f} tok/s, "
          f"decode_impl={args.decode_impl}, "
          f"kv={'int8' if args.int8_kv else 'bf16'})")
    for r in finished[:4]:
        print(f"  req {r.rid}: prompt {len(r.prompt):3d} tok -> "
              f"{len(r.generated):2d} new [{r.finish_reason}] "
              f"{r.generated[:8]} ...")


if __name__ == "__main__":
    main()
