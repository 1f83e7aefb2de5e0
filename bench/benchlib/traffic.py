"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into the requests of one run.

A mix states its prompt lengths as integer weights, the tokens each
request asks for, and where prompts come from:

* ``"pool": n`` — a pool of ``n`` distinct prompts, requested by rank
  with Zipf popularity ``zipf_s``; the rank sequence comes from the mix's
  own ``order_seed``, so every run seed sends the same repeats in the same
  places and differs only in the prompts and which length each rank has;
* ``"pool": null`` — every request is a new prompt.

Lengths are dealt in shuffled blocks that hold each length exactly in the
ratio of its weight, so every seed sends the same mix of sizes in another
order. Warm-up prompts come from a stream of their own, one per length,
and never occur in the traffic.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Iterator

import numpy as np

#: the most requests a run can send; a closed loop pulls a prefix of them,
#: and only the requests it pulls are made
STREAM = 20000


@dataclasses.dataclass(frozen=True)
class Request:
    prompt_id: int
    prompt: np.ndarray          # int32 token ids
    new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _dealt_lengths(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    block = np.repeat([int(k) for k in mix["prompt_lengths"]],
                      [int(v) for v in mix["prompt_lengths"].values()])
    reps = -(-n // len(block))
    return np.concatenate([rng.permutation(block) for _ in range(reps)])[:n]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, n, dtype=np.int32)


def requests(mix: dict, seed: int, vocab: int,
             count: int = STREAM) -> Iterator[Request]:
    """The run's request sequence, in the order a closed loop sends it;
    each new prompt is drawn when the loop pulls its request."""
    new = int(mix["new_tokens"])
    pool = mix.get("pool")
    rng = _rng(seed, 1)
    if pool is None:
        lens = _dealt_lengths(mix, count, rng)
        for i, n in enumerate(lens):
            yield Request(i, _tokens(rng, int(n), vocab), new)
        return
    lens = _dealt_lengths(mix, pool, rng)
    prompts = [_tokens(rng, int(n), vocab) for n in lens]
    ranks = np.arange(1, pool + 1, dtype=np.float64)
    p = ranks ** -float(mix["zipf_s"])
    order = np.random.default_rng(int(mix["order_seed"])).choice(
        pool, size=count, p=p / p.sum())
    for r in order:
        yield Request(int(r), prompts[int(r)], new)


def warmup(mix: dict, seed: int, vocab: int) -> list[Request]:
    """One request per prompt length, from a stream the traffic never
    uses; a pooled mix sends its first warm-up prompt twice so that the
    cache-hit path is warm too."""
    rng = _rng(seed, 2)
    new = int(mix["new_tokens"])
    out = [Request(-1 - i, _tokens(rng, int(n), vocab), new)
           for i, n in enumerate(mix["prompt_lengths"])]
    if mix.get("pool") is not None:
        out.append(out[0])
    return out


def longest(mix: dict) -> int:
    """The longest sequence a request of this mix reaches."""
    return max(int(k) for k in mix["prompt_lengths"]) + int(mix["new_tokens"])
