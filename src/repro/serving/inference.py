"""Provenance-tracked LM generation as a first-class engine workload.

:func:`generate` is a calcfunction: every generation is a process node
whose inputs (architecture, parameter seed, prompt tokens, decode
settings) are content-fingerprinted by the caching layer exactly like
any other calculation. Greedy decoding makes the mapping
``(arch, seed, prompt, settings) -> continuation`` a pure function, so

* with caching enabled, an identical prompt is served from the
  provenance graph with **zero decode steps** — the cache-hit fast path
  clones the stored ``tokens``/``stats`` outputs without touching jax;
* generations travel in archives and serve hits across profiles, like
  every other finished-ok calculation.

The execution side is a per-OS-process :class:`ServingEngine` memo: one
compiled :class:`~repro.serving.serve.BatchScheduler` per (arch, seed,
cache size) that cold prompts are batched through. The model is the
published ``arch`` config unless the call asks for ``reduced`` (the
CPU-sized test config); the size is a calcfunction input, so it enters
the provenance fingerprint and a reduced generation is never served for a
published one. Decode runs through the Pallas flash-decode kernel
(``decode_impl='pallas'``, interpreted off-TPU) so the serving hot loop
exercises the same kernel the TPU path runs.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any

import numpy as np

from repro.core.datatypes import ArrayData, Bool, Dict, Int, Str
from repro.core.process_functions import calcfunction
from repro.observability import trace
from repro.serving.serve import BatchScheduler, Request

#: serving defaults (tests ask for the reduced size of this arch)
DEFAULT_ARCH = "aiida-demo-110m"
DEFAULT_BATCH_SIZE = 4
_MIN_CACHE = 128

_ENGINES: dict[tuple, "ServingEngine"] = {}


def _serving_config(arch: str, reduced: bool, decode_impl: str):
    from repro.configs import get_config, reduced_config

    cfg = reduced_config(arch) if reduced else get_config(arch)
    return cfg.replace(decode_impl=decode_impl)


class ServingEngine:
    """One compiled scheduler + params, reused across generate() calls."""

    def __init__(self, arch: str, seed: int, max_len: int,
                 batch_size: int = DEFAULT_BATCH_SIZE,
                 eos_id: int = -1, decode_impl: str = "pallas",
                 reduced: bool = False):
        import jax

        from repro.models.registry import build

        self.arch, self.seed = arch, int(seed)
        self.cfg = _serving_config(arch, bool(reduced), decode_impl)
        self.bundle = build(self.cfg)
        # drawn and stored in the compute dtype leaf by leaf: no float32
        # copy of the weights stays on the device
        self.scheduler = BatchScheduler(
            self.bundle,
            self.bundle.init_serving_params(jax.random.PRNGKey(int(seed))),
            batch_size=batch_size, max_len=max_len, eos_id=eos_id)
        self.params = self.scheduler.params
        self._next_rid = 0

    def generate_many(self, prompts: list[list[int]],
                      max_new_tokens: int) -> list[Request]:
        """Continuous-batch a whole prompt list; results in request order."""
        reqs = []
        for p in prompts:
            req = Request(rid=self._next_rid, prompt=list(map(int, p)),
                          max_new_tokens=int(max_new_tokens))
            self._next_rid += 1
            self.scheduler.submit(req)
            reqs.append(req)
        self.scheduler.run()
        return reqs

    def generate_one(self, prompt: list[int], max_new_tokens: int) -> Request:
        return self.generate_many([prompt], max_new_tokens)[0]


def get_engine(arch: str = DEFAULT_ARCH, seed: int = 0, *,
               reduced: bool = False, need_len: int = _MIN_CACHE,
               batch_size: int = DEFAULT_BATCH_SIZE, eos_id: int = -1,
               decode_impl: str = "pallas") -> ServingEngine:
    """Memoised engine; ``need_len`` is bucketed to a power of two so one
    compiled cache serves a band of request sizes."""
    max_len = _MIN_CACHE
    while max_len < int(need_len) + 1:
        max_len *= 2
    key = (arch, int(seed), bool(reduced), max_len, batch_size, eos_id,
           decode_impl)
    eng = _ENGINES.get(key)
    if eng is None:
        eng = _ENGINES[key] = ServingEngine(
            arch, seed, max_len, batch_size=batch_size, eos_id=eos_id,
            decode_impl=decode_impl, reduced=reduced)
    return eng


def reset_engines() -> None:
    """Drop the compiled-engine memo (test isolation)."""
    _ENGINES.clear()


def prompt_fingerprint(arch: str, seed: int, prompt: Any,
                       reduced: bool = False) -> str:
    """The serving-side prompt-prefix fingerprint: sha256 over the model
    identity (arch, size, seed) and the exact prompt token sequence. Two
    requests with the same fingerprint are guaranteed the same
    continuation (greedy), which is the property the content-addressed
    cache exploits."""
    toks = np.asarray(prompt, np.int32)
    size = "reduced" if reduced else "published"
    h = hashlib.sha256()
    h.update(f"{arch}|{size}|{int(seed)}|".encode())
    h.update(toks.tobytes())
    return h.hexdigest()


@calcfunction
def generate(arch: Str, prompt: ArrayData, max_new_tokens: Int,
             seed: Int, eos_id: Int, reduced: Bool = lambda: Bool(False)):
    """Greedy continuation of ``prompt`` under the ``arch`` model — its
    published config, or the CPU-sized one when ``reduced`` — with
    parameters drawn from ``seed``. Returns the generated tokens plus a
    stats document; both are provenance outputs, so identical calls are
    cache hits that never re-decode."""
    toks = [int(t) for t in np.asarray(prompt.value).reshape(-1)]
    new = int(max_new_tokens.value)
    small = bool(reduced.value)
    eng = get_engine(str(arch.value), int(seed.value), reduced=small,
                     need_len=len(toks) + new, eos_id=int(eos_id.value))
    t0 = time.monotonic()
    with trace.span("serving.generate"):
        req = eng.generate_many([toks], new)[0]
    dt = time.monotonic() - t0
    return {
        "tokens": ArrayData(np.asarray(req.generated, np.int32)),
        "stats": Dict({
            "prompt_tokens": len(toks),
            "new_tokens": len(req.generated),
            "finish_reason": req.finish_reason,
            "fingerprint": prompt_fingerprint(str(arch.value),
                                              int(seed.value), toks, small),
            "wall_seconds": dt,
        }),
    }
