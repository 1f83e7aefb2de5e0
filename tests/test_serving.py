"""Continuous-batching scheduler, prefill/decode parity, sharded-serving
equivalence, and the provenance-cached generate() workload.

Everything runs on CPU: the Pallas decode path executes in interpret mode,
and the multi-device test forces fake host devices in a subprocess (the
main pytest process keeps its single CPU device).
"""

import json
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.registry import build
from repro.serving.serve import (BatchScheduler, Request, make_decode_step,
                                 make_prefill_step)

ARCH = "aiida-demo-110m"
RNG = np.random.default_rng(7)


def _build(decode_impl="direct", **over):
    cfg = reduced_config(ARCH).replace(
        dtype="float32", decode_impl=decode_impl, **over)
    bundle = build(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lm():
    return _build()


def _prompts(n, length=6):
    return [RNG.integers(1, 500, length).tolist() for _ in range(n)]


def _serve(bundle, params, prompts, new_tokens, *, batch=2, max_len=64,
           eos_id=-1):
    sched = BatchScheduler(bundle, params, batch_size=batch,
                           max_len=max_len, eos_id=eos_id)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched, reqs


# ---------------------------------------------------------------------------
# BatchScheduler unit tests
# ---------------------------------------------------------------------------

def test_max_new_tokens_enforced(lm):
    _, reqs = _serve(*lm, _prompts(3), new_tokens=5)
    for r in reqs:
        assert r.done and r.finish_reason == "length"
        assert len(r.generated) == 5


def test_slot_reuse_after_eos(lm):
    prompts = _prompts(2)
    # discover what the model actually says, then make token #2 the EOS
    _, probe = _serve(*lm, [prompts[0]], new_tokens=4)
    eos = probe[0].generated[1]
    sched, reqs = _serve(*lm, prompts, new_tokens=8, batch=1, eos_id=eos)
    assert reqs[0].finish_reason == "eos"
    assert len(reqs[0].generated) <= 2
    assert reqs[1].done                       # queued request got the slot
    assert reqs[1].started_at >= reqs[0].finished_at
    assert all(s is None for s in sched.slots)


def test_fifo_admission_under_full_batch(lm):
    _, reqs = _serve(*lm, _prompts(6), new_tokens=4, batch=2)
    starts = [r.started_at for r in reqs]
    assert starts == sorted(starts), \
        "admission must follow submission order (FIFO)"
    assert all(r.done for r in reqs)


def test_determinism_under_fixed_seed(lm):
    prompts = _prompts(4)
    _, a = _serve(*lm, prompts, new_tokens=6, batch=2)
    _, b = _serve(*lm, prompts, new_tokens=6, batch=2)
    assert [r.generated for r in a] == [r.generated for r in b]


def test_cobatched_neighbors_do_not_leak(lm):
    """A request's tokens must not depend on what shares its micro-batch."""
    prompts = _prompts(4)
    _, alone = _serve(*lm, [prompts[0]], new_tokens=6, batch=4)
    _, crowd = _serve(*lm, prompts, new_tokens=6, batch=4)
    assert alone[0].generated == crowd[0].generated


def test_cache_full_eviction(lm):
    bundle, params = lm
    sched = BatchScheduler(bundle, params, batch_size=1, max_len=16)
    req = Request(rid=0, prompt=_prompts(1, length=12)[0],
                  max_new_tokens=100)
    sched.submit(req)
    sched.run()
    assert req.done and req.finish_reason == "cache_full"
    assert len(req.generated) < 100


def test_oversized_prompt_rejected(lm):
    bundle, params = lm
    sched = BatchScheduler(bundle, params, batch_size=1, max_len=16)
    with pytest.raises(ValueError, match="cannot fit"):
        sched.submit(Request(rid=0, prompt=list(range(1, 17)),
                             max_new_tokens=1))


def test_max_pending_rejects_with_counter(lm):
    from repro.observability.metrics import get_registry
    from repro.serving.serve import QueueFullError

    bundle, params = lm
    sched = BatchScheduler(bundle, params, batch_size=1, max_len=16,
                           max_pending=2)
    reqs = [Request(rid=i, prompt=[1, 2, 3], max_new_tokens=1)
            for i in range(3)]
    sched.submit(reqs[0])
    sched.submit(reqs[1])
    rejected = get_registry().counter("serving.rejected")
    before = rejected.value
    with pytest.raises(QueueFullError, match="max_pending=2"):
        sched.submit(reqs[2])
    assert rejected.value == before + 1
    # the bound is backpressure, not a death sentence: once the queue
    # drains the same request is admissible again
    sched.run()
    assert reqs[0].done and reqs[1].done
    sched.submit(reqs[2])
    assert len(sched.queue) == 1


def test_max_pending_validation(lm):
    bundle, params = lm
    with pytest.raises(ValueError, match="max_pending"):
        BatchScheduler(bundle, params, batch_size=1, max_len=16,
                       max_pending=0)


def test_recurrent_family_rejected():
    cfg = reduced_config("recurrentgemma-2b")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="recurrent"):
        BatchScheduler(bundle, params, batch_size=1, max_len=16)


def test_pallas_decode_matches_direct(lm):
    """The flash-decode kernel routing is numerically interchangeable with
    the masked-einsum path at serving time (greedy tokens identical)."""
    prompts = _prompts(3)
    _, direct = _serve(*lm, prompts, new_tokens=6, batch=2)
    pallas = _build(decode_impl="pallas")
    _, routed = _serve(*pallas, prompts, new_tokens=6, batch=2)
    assert [r.generated for r in direct] == [r.generated for r in routed]


# ---------------------------------------------------------------------------
# the one-step-ahead decode loop
# ---------------------------------------------------------------------------

def _counts():
    from repro.observability.metrics import get_registry

    reg = get_registry()
    return {k: reg.counter(f"serving.{k}").value
            for k in ("decode_steps", "steps_ahead", "overrun_steps")}


def _since(before):
    return {k: v - before[k] for k, v in _counts().items()}


def _serial(bundle, params, prompt, new_tokens, *, max_len=64, eos_id=-1):
    """One request alone: prefill, then ``make_decode_step`` with its token
    fetched after every step, stopped by EOS, length or a full cache."""
    prefill = jax.jit(make_prefill_step(bundle))
    decode = jax.jit(make_decode_step(bundle))
    tok, cache = prefill(params,
                         {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                         bundle.init_cache(1, max_len))
    out, pos = [int(jax.device_get(tok)[0, 0])], len(prompt)
    while (out[-1] != eos_id and len(out) < new_tokens
           and pos < max_len - 1):
        tok, cache = decode(params, cache, tok, jnp.asarray([pos], jnp.int32))
        out.append(int(jax.device_get(tok)[0, 0]))
        pos += 1
    return out


def _late_eos(tokens):
    """A token first produced by a decode step (not by the prefill)."""
    return next(t for k, t in enumerate(tokens) if k and t not in tokens[:k])


@pytest.mark.parametrize("eos", [False, True], ids=["eos_off", "eos_on"])
def test_scheduler_tokens_equal_a_serial_loop(lm, eos):
    """Five prompts of mixed lengths over four slots: every request's
    tokens equal a plain loop over ``make_decode_step`` that fetches after
    every step; with EOS on, an EOS ends a request mid-batch and its slot
    takes the fifth request while a step is in flight."""
    bundle, params = lm
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 500, n).tolist() for n in (3, 9, 5, 12, 7)]
    news = [6, 10, 4, 8, 9]
    eos_id = -1
    if eos:
        eos_id = _late_eos(_serial(bundle, params, prompts[0], news[0]))
    want = [_serial(bundle, params, p, n, eos_id=eos_id)
            for p, n in zip(prompts, news)]
    sched = BatchScheduler(bundle, params, batch_size=4, max_len=64,
                           eos_id=eos_id)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, news))]
    before = _counts()
    for r in reqs:
        sched.submit(r)
    sched.run()
    got = _since(before)
    assert [r.generated for r in reqs] == want
    assert all(r.done for r in reqs) and sched._in_flight is None
    # the loop starts from nothing once, and every later step is ahead
    assert got["steps_ahead"] == got["decode_steps"] - 1
    if eos:
        assert reqs[0].finish_reason == "eos"
        assert got["overrun_steps"] >= 1
    else:
        assert {r.finish_reason for r in reqs} == {"length"}
        assert got["overrun_steps"] == 0


def test_late_eos_drops_the_overrun_token_and_readmits(lm):
    """One slot: the EOS of the first request is learned while the step
    after it runs; that step's token is dropped and counted, and the
    second request, admitted while it is in flight, gets its own tokens."""
    bundle, params = lm
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 500, 6).tolist() for _ in range(2)]
    eos_id = _late_eos(_serial(bundle, params, prompts[0], 8))
    want = [_serial(bundle, params, p, 8, eos_id=eos_id) for p in prompts]
    before = _counts()
    sched, reqs = _serve(bundle, params, prompts, 8, batch=1, eos_id=eos_id)
    got = _since(before)
    assert [r.generated for r in reqs] == want
    assert reqs[0].finish_reason == "eos" and reqs[0].generated[-1] == eos_id
    assert reqs[1].started_at >= reqs[0].finished_at
    late = sum(r.finish_reason == "eos" and len(r.generated) < 8
               for r in reqs)
    assert got["overrun_steps"] == late >= 1
    # the steps that ran: each request's, and one past each late EOS
    assert got["decode_steps"] == sum(len(r.generated) - 1
                                      for r in reqs) + late
    # the second request's first step was launched with the overrun step
    # still in flight, so the loop started from nothing only once
    assert got["steps_ahead"] == got["decode_steps"] - 1


@pytest.mark.parametrize("case", ["max_new_tokens_1", "cache_full"])
def test_lookahead_launches_no_step_past_a_known_end(lm, case):
    """A request that ends by length or a full cache ends as a serial loop
    ends it, and no step is launched past it: the device positions stop
    where the last step left them."""
    bundle, params = lm
    rng = np.random.default_rng(29)
    if case == "max_new_tokens_1":
        prompts, news, max_len = [rng.integers(1, 500, 5).tolist()
                                  for _ in range(3)], [1, 1, 1], 64
    else:   # one row fills the cache, its neighbour ends by length
        prompts = [rng.integers(1, 500, n).tolist() for n in (12, 5)]
        news, max_len = [100, 3], 16
    sched = BatchScheduler(bundle, params, batch_size=2, max_len=max_len)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, news))]
    before = _counts()
    for r in reqs:
        sched.submit(r)
    sched.run()
    got = _since(before)
    assert [r.generated for r in reqs] == [
        _serial(bundle, params, p, n, max_len=max_len)
        for p, n in zip(prompts, news)]
    assert got["overrun_steps"] == 0
    if case == "max_new_tokens_1":
        assert {r.finish_reason for r in reqs} == {"length"}
        assert got["decode_steps"] == got["steps_ahead"] == 0
    else:
        assert [r.finish_reason for r in reqs] == ["cache_full", "length"]
        assert len(reqs[0].generated) == max_len - len(prompts[0])
        assert got["decode_steps"] == len(reqs[0].generated) - 1
        assert np.asarray(sched.pos).tolist() == [
            max_len - 1, len(prompts[1]) + news[1] - 1]


def test_decode_steps_and_steps_ahead_per_request(lm):
    """EOS off, one request at a time: a request of n tokens runs n - 1
    decode steps, and all but its first are launched ahead."""
    bundle, params = lm
    rng = np.random.default_rng(31)
    news = [2, 5, 9, 16]
    sched = BatchScheduler(bundle, params, batch_size=4, max_len=64)
    before = _counts()
    for i, n in enumerate(news):
        sched.submit(Request(rid=i, prompt=rng.integers(1, 500, 7).tolist(),
                             max_new_tokens=n))
        assert [len(r.generated) for r in sched.run()] == [n]
    got = _since(before)
    assert got["decode_steps"] == sum(n - 1 for n in news)
    assert got["steps_ahead"] == got["decode_steps"] - len(news)
    assert got["overrun_steps"] == 0


def _kv_block_counts(monkeypatch, bundle, params, prompt, new, *, batch=4,
                     max_len=64):
    """Serve one request through a scheduler that counts into a registry
    of its own; returns that registry's counters."""
    from repro.observability.metrics import MetricsRegistry
    from repro.serving import serve

    reg = MetricsRegistry()
    monkeypatch.setattr(serve, "get_registry", lambda: reg)
    _serve(bundle, params, [prompt], new, batch=batch, max_len=max_len)
    return reg.snapshot()["counters"]


def test_scheduler_counts_the_kv_blocks_the_dense_kernel_reads(monkeypatch):
    """One long row beside three empty slots, blocks of 16 over a cache of
    64: the 4 decode steps of a 30-token prompt attend to 31, 32, 33 and
    34 positions, 2, 2, 3 and 3 blocks of the live row, and block 0 of
    each empty row, in each of the 2 layers, out of a grid of 4 rows x 4
    blocks a step."""
    bundle, params = _build("pallas", attn_kv_block=16)
    got = _kv_block_counts(monkeypatch, bundle, params,
                           RNG.integers(1, 500, 30).tolist(), 5)
    assert bundle.cfg.num_layers == 2
    assert got["serving.decode_steps"] == 4
    assert got["attn.kv_blocks_read"] == 2 * ((2 + 2 + 3 + 3) + 4 * 3)
    assert got["attn.kv_blocks_grid"] == 4 * 2 * (4 * 4)


@pytest.mark.parametrize("arch,decode_impl", [
    ("moonlight-16b-a3b", "pallas"),     # latent cache and expert layers
    (ARCH, "direct"),                    # the masked einsum
])
def test_scheduler_counts_no_kv_blocks_without_the_dense_kernel(
        monkeypatch, arch, decode_impl):
    """A model whose decode does not run the dense kernel registers
    neither counter."""
    cfg = reduced_config(arch).replace(dtype="float32",
                                       decode_impl=decode_impl)
    bundle = build(cfg)
    got = _kv_block_counts(monkeypatch, bundle,
                           bundle.init_params(jax.random.PRNGKey(0)),
                           RNG.integers(1, 500, 9).tolist(), 3)
    assert got["serving.decode_steps"] == 2
    assert "attn.kv_blocks_read" not in got
    assert "attn.kv_blocks_grid" not in got


# ---------------------------------------------------------------------------
# prefill/decode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["aiida-demo-110m", "recurrentgemma-2b",
                                  "granite-4.0-h-small",
                                  "kimi-linear-48b-a3b"])
def test_prefill_equals_stepwise_decode(arch):
    """Prefilling N tokens must land in the same state as feeding those N
    tokens one decode step at a time: identical next token and identical
    greedy continuation."""
    cfg = reduced_config(arch).replace(dtype="float32")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(1))
    prefill = jax.jit(make_prefill_step(bundle))
    decode = jax.jit(make_decode_step(bundle))
    n, extra, max_len = 8, 4, 32
    prompt = jnp.asarray(RNG.integers(1, cfg.vocab_size, (1, n)), jnp.int32)

    def continue_greedy(tok, cache, pos):
        seq = [int(np.asarray(tok)[0, 0])]
        for i in range(extra):
            tok, cache = decode(params, cache, tok,
                                jnp.asarray(pos + i, jnp.int32))
            seq.append(int(np.asarray(tok)[0, 0]))
        return seq

    tok_a, cache_a = prefill(params, {"tokens": prompt},
                             bundle.init_cache(1, max_len))
    seq_a = continue_greedy(tok_a, cache_a, n)

    tok_b, cache_b = prefill(params, {"tokens": prompt[:, :1]},
                             bundle.init_cache(1, max_len))
    for i in range(1, n):
        tok_b, cache_b = decode(params, cache_b, prompt[:, i:i + 1],
                                jnp.asarray(i, jnp.int32))
    seq_b = continue_greedy(tok_b, cache_b, n)
    assert seq_a == seq_b


# ---------------------------------------------------------------------------
# sharded serving equivalence (fake multi-device CPU, subprocess)
# ---------------------------------------------------------------------------

SHARDED_SERVE_PROG = textwrap.dedent("""
    import sys
    sys.path.insert(0, {src!r})
    from repro.configs import make_serving_mesh, reduced_config, setup_devices
    devs = setup_devices(platform="cpu", n_devices=2)
    assert len(devs) == 2, devs
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.sharding import make_rules
    from repro.models.common import axis_rules
    from repro.models.registry import build
    from repro.serving.serve import make_decode_step, make_prefill_step

    cfg = reduced_config("aiida-demo-110m").replace(
        dtype="float32", decode_impl="pallas")
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 8)), jnp.int32)

    def run(mesh_rules):
        prefill = jax.jit(make_prefill_step(bundle))
        decode = jax.jit(make_decode_step(bundle))

        def body():
            cache = bundle.init_cache(2, 32)
            tok, cache = prefill(params, {{"tokens": prompt}}, cache)
            toks = [np.asarray(tok)]
            pos = np.array([8, 8], np.int32)
            for _ in range(4):
                tok, cache = decode(params, cache, tok,
                                    jnp.asarray(pos, jnp.int32))
                toks.append(np.asarray(tok))
                pos += 1
            return np.concatenate(toks, axis=1)

        if mesh_rules is None:
            return body()
        with axis_rules(*mesh_rules):
            return body()

    single = run(None)
    mesh = make_serving_mesh(data=1, model=2)
    rules = make_rules(cfg, mesh, fsdp=False)
    sharded = run((mesh, rules))
    print("RESULT:" + json.dumps({{
        "ok": bool((single == sharded).all()),
        "single": single.tolist(), "sharded": sharded.tolist(),
        "heads_rule": str(rules["heads"]),
    }}))
""")


def test_sharded_decode_matches_single_device():
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = SHARDED_SERVE_PROG.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    result = json.loads(line[0][len("RESULT:"):])
    assert result["heads_rule"] == "model"    # heads really were sharded
    assert result["ok"], result


# ---------------------------------------------------------------------------
# provenance-cached generation workload
# ---------------------------------------------------------------------------

def test_generate_cache_hit_runs_zero_decode_steps(runner):
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.observability.metrics import get_registry
    from repro.serving.inference import (generate, prompt_fingerprint,
                                         reset_engines)

    reset_engines()
    steps = get_registry().counter("serving.decode_steps")
    prompt = [3, 5, 7, 11, 13]

    def call():
        return generate(Str(ARCH), ArrayData(np.asarray(prompt, np.int32)),
                        Int(4), Int(0), Int(-1), Bool(True))

    with enable_caching():
        cold = call()
        before = steps.value
        hot = call()
    assert steps.value == before, "cache hit must not touch the decoder"
    np.testing.assert_array_equal(np.asarray(cold["tokens"].value),
                                  np.asarray(hot["tokens"].value))
    stats = hot["stats"].value
    assert stats["new_tokens"] == len(np.asarray(hot["tokens"].value))
    assert stats["fingerprint"] == prompt_fingerprint(ARCH, 0, prompt,
                                                      reduced=True)


def test_generate_distinct_prompts_do_not_collide(runner):
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.serving.inference import generate, reset_engines

    reset_engines()
    with enable_caching():
        a = generate(Str(ARCH), ArrayData(np.asarray([1, 2, 3], np.int32)),
                     Int(4), Int(0), Int(-1), Bool(True))
        b = generate(Str(ARCH), ArrayData(np.asarray([1, 2, 4], np.int32)),
                     Int(4), Int(0), Int(-1), Bool(True))
    fa = a["stats"].value["fingerprint"]
    fb = b["stats"].value["fingerprint"]
    assert fa != fb


def test_engine_memo_buckets_by_cache_size():
    from repro.serving.inference import get_engine, reset_engines

    reset_engines()
    e1 = get_engine(ARCH, 0, reduced=True, need_len=10)
    e2 = get_engine(ARCH, 0, reduced=True, need_len=100)  # same 128 bucket
    e3 = get_engine(ARCH, 0, reduced=True, need_len=200)  # next power of 2
    assert e1 is e2
    assert e3 is not e1
    assert e3.scheduler.max_len == 256


def test_model_size_enters_the_fingerprint():
    from repro.serving.inference import prompt_fingerprint

    prompt = [3, 5, 7]
    assert (prompt_fingerprint(ARCH, 0, prompt, reduced=True)
            != prompt_fingerprint(ARCH, 0, prompt, reduced=False))


def test_reduced_generation_is_not_served_for_published(runner, monkeypatch):
    """The size is a calcfunction input: a cached reduced generation is no
    hit for the published size of the same arch, nor the other way round.
    The published engine is stood in by the reduced one (same interface),
    so the test stays CPU-sized while the provenance inputs differ."""
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.observability.metrics import get_registry
    from repro.serving import inference

    inference.reset_engines()
    real_get_engine = inference.get_engine
    sizes = []

    def cpu_sized(arch, seed, *, reduced=False, **kw):
        sizes.append(reduced)
        return real_get_engine(arch, seed, reduced=True, **kw)

    monkeypatch.setattr(inference, "get_engine", cpu_sized)
    steps = get_registry().counter("serving.decode_steps")
    prompt = ArrayData(np.asarray([2, 4, 6, 8], np.int32))

    def call(*reduced):
        before = steps.value
        out = inference.generate(Str(ARCH), prompt, Int(3), Int(0), Int(-1),
                                 *reduced)
        return out, steps.value - before

    with enable_caching():
        small, ran_small = call(Bool(True))
        big, ran_big = call()                      # default: published
        _, ran_small_again = call(Bool(True))
        _, ran_big_again = call(Bool(False))
    assert sizes == [True, False]                  # the hits built nothing
    assert ran_small > 0 and ran_big > 0, "a size was served another's cache"
    assert ran_small_again == 0 and ran_big_again == 0
    assert (small["stats"].value["fingerprint"]
            != big["stats"].value["fingerprint"])


def test_serving_spans_one_admit_per_request_one_step_per_fetch():
    from repro.observability import trace
    from repro.observability.metrics import get_registry
    from repro.serving.inference import get_engine, reset_engines

    reset_engines()
    eng = get_engine(ARCH, 0, reduced=True, need_len=16)
    steps = get_registry().counter("serving.decode_steps")
    prompts = _prompts(5)   # one more than the engine's slots
    trace.enable()
    try:
        before = steps.value
        with trace.capture() as tl:
            eng.generate_many(prompts, 3)
        counted = steps.value - before
    finally:
        trace.reset()
    names = [s.name for s in tl.spans]
    assert names.count("serving.admit") == len(prompts)
    step_ids = {s.span_id for s in tl.spans if s.name == "serving.step"}
    holding_fetch = {s.parent_id for s in tl.spans
                     if s.name == "serving.fetch"}
    assert holding_fetch <= step_ids
    assert len(holding_fetch) == counted > 0


def test_generate_timeline_persists_request_spans_not_step_spans(runner):
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.engine.launch import run_get_node
    from repro.observability import trace
    from repro.observability.timeline import load_spans, render_timeline
    from repro.serving.inference import generate, reset_engines

    reset_engines()
    trace.enable()
    try:
        _res, node = run_get_node(
            generate.process_class, arch=Str(ARCH),
            prompt=ArrayData(np.asarray([3, 5, 7, 11], np.int32)),
            max_new_tokens=Int(4), seed=Int(0), eos_id=Int(-1),
            reduced=Bool(True))
    finally:
        trace.reset()
    spans = load_spans(runner.store, node.pk)
    names = [s["name"] for s in spans]
    assert names.count("serving.generate") == 1
    assert names.count("serving.admit") == 1
    assert not {"serving.step", "serving.fetch"} & set(names)
    # the admission's parent is the unpersisted step: drawn as a root
    admit = next(s for s in spans if s["name"] == "serving.admit")
    assert admit["parent"] is not None
    assert admit["parent"] not in {s["id"] for s in spans}
    assert "serving.admit" in render_timeline(spans)


# ---------------------------------------------------------------------------
# weights stored in the compute dtype
# ---------------------------------------------------------------------------

#: one reduced config per served family; qwen3-4b adds the q/k norms,
#: moonlight the latent attention, the leading dense layer and the
#: no-drop expert layer over held experts, granite-4.0-h the Mamba-2
#: layers and softmax routing, kimi-linear the KDA layers with a dense
#: first layer among them
SERVED_ARCHS = ("qwen2-0.5b", "qwen3-4b", "moonshot-v1-16b-a3b",
                "llava-next-34b", "moonlight-16b-a3b", "granite-4.0-h-small",
                "kimi-linear-48b-a3b")
#: leaves the forward reads in float32, by name (independent of the specs)
F32_LEAF_NAMES = ("ln_attn", "ln_mlp", "ln_final", "q_norm", "k_norm",
                  "router", "router_bias", "kv_norm", "ln_ssm", "dt_bias",
                  "A_log", "D", "norm", "ln_kda", "o_norm")


def _named_leaves(tree):
    return [(str(getattr(path[-1], "key", path[-1])), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _served_model(arch, **over):
    """Reduced config and float32 masters whose norm scales are off 1, so
    a norm scale rounded to bfloat16 would show in the logits."""
    cfg = reduced_config(arch).replace(**over)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)

    def jitter(path, leaf):
        if str(getattr(path[-1], "key", "")) not in F32_LEAF_NAMES:
            return leaf
        return leaf * (1 + 0.05 * rng.standard_normal(leaf.shape,
                                                      np.float32))

    return cfg, bundle, jax.tree_util.tree_map_with_path(jitter, params)


def test_served_archs_cover_every_served_family():
    from repro.models.registry import LM_FAMILIES

    assert {reduced_config(a).family for a in SERVED_ARCHS} == \
        set(LM_FAMILIES)


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_serving_params_give_the_float32_masters_logits(arch):
    """The forward rounds each matrix to bfloat16 at use; storing it
    rounded gives the same bits to multiply, so the logits are equal bit
    for bit, in prefill and in the decode step after it."""
    from repro.models.registry import serving_params

    cfg, bundle, params = _served_model(arch)
    assert cfg.activation_dtype == jnp.bfloat16
    served = serving_params(cfg, params)
    n = 8
    batch = {"tokens": jnp.asarray(RNG.integers(1, cfg.vocab_size, (1, n)),
                                   jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            RNG.normal(size=(1, cfg.num_patches, cfg.d_model)), jnp.bfloat16)
        n += cfg.num_patches
    prefill, decode = jax.jit(bundle.prefill_fn), jax.jit(bundle.decode_fn)
    out = {}
    for name, tree in (("masters", params), ("served", served)):
        logits, cache = prefill(tree, batch, bundle.init_cache(1, 32))
        tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], -1).astype(jnp.int32)
        step, _ = decode(tree, cache, tok, jnp.asarray([n], jnp.int32))
        out[name] = (np.asarray(logits, np.float32),
                     np.asarray(step, np.float32))
    np.testing.assert_array_equal(out["served"][0], out["masters"][0])
    np.testing.assert_array_equal(out["served"][1], out["masters"][1])


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_serving_params_keep_float32_leaves(arch):
    from repro.models.registry import serving_params

    cfg, _, params = _served_model(arch)
    for name, leaf in _named_leaves(serving_params(cfg, params)):
        want = jnp.float32 if name in F32_LEAF_NAMES else jnp.bfloat16
        assert leaf.dtype == want, name


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_serving_params_change_nothing_at_float32(arch):
    from repro.models.registry import serving_params

    cfg, _, params = _served_model(arch, dtype="float32")
    served = serving_params(cfg, params)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(params)):
        assert a is b


def test_serving_params_reject_an_unserved_family():
    from repro.models.registry import serving_params

    cfg = reduced_config("recurrentgemma-2b")
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="serving parameters"):
        serving_params(cfg, params)


def test_init_serving_params_is_the_cast_of_init_params():
    from repro.models.registry import serving_params

    cfg = reduced_config("qwen2-0.5b")
    bundle = build(cfg)
    drawn = bundle.init_serving_params(jax.random.PRNGKey(3))
    want = serving_params(cfg, bundle.init_params(jax.random.PRNGKey(3)))
    assert jax.tree.structure(drawn) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


_F32_TO_BF16 = re.compile(r"stablehlo\.convert %\S+ : "
                          r"\(tensor<([0-9x]+)xf32>\) -> tensor<[0-9x]+xbf16>")


def _weight_converts(text, shapes):
    return {s for s in _F32_TO_BF16.findall(text) if s in shapes}


def test_step_programs_convert_no_weight():
    """Lowered with the scheduler's own tree, neither step converts a
    weight (stacked, or one layer's slice inside the scan); lowered with
    the float32 masters, both convert every one of them."""
    # six query heads over two KV heads: no activation of either step
    # shares a weight's shape, so a convert by shape is a weight's
    cfg, bundle, params = _served_model("qwen2-0.5b", num_heads=6,
                                        decode_impl="pallas")
    sched = BatchScheduler(bundle, params, batch_size=3, max_len=64)
    shapes = set()
    for name, leaf in _named_leaves(params):
        if name not in F32_LEAF_NAMES:
            shapes.add("x".join(map(str, leaf.shape)))
            shapes.add("x".join(map(str, leaf.shape[1:])))
    prompt = {"tokens": jnp.zeros((1, 7), jnp.int32)}

    def lowered(tree):
        return (sched.decode_step.lower(tree, sched.cache, sched.tokens,
                                        sched.pos, sched.active).as_text(),
                sched.prefill_step.lower(tree, prompt,
                                         bundle.init_cache(1, 64)).as_text())

    for text in lowered(sched.params):
        assert _weight_converts(text, shapes) == set()
    per_leaf = {"x".join(map(str, leaf.shape[1:] if name != "embedding"
                             else leaf.shape))
                for name, leaf in _named_leaves(params)
                if name not in F32_LEAF_NAMES}
    for text in lowered(params):
        assert _weight_converts(text, shapes) >= per_leaf


def test_assigned_tree_is_stored_cast_and_gauged():
    """The benchmark's pattern, ``eng.params = eng.scheduler.params =
    tree``: a float32 tree comes out cast, a cast tree passes as it is
    (its buffers, so nothing recompiles), and the gauge reads the bytes."""
    from repro.observability.metrics import get_registry

    cfg, bundle, params = _served_model("qwen2-0.5b")
    gauge = get_registry().gauge("serving.weight_bytes")
    f32_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    sched = BatchScheduler(bundle, bundle.init_serving_params(
        jax.random.PRNGKey(0)), batch_size=1, max_len=16)
    sched.params = params
    served = sched.params
    for name, leaf in _named_leaves(served):
        want = jnp.float32 if name in F32_LEAF_NAMES else jnp.bfloat16
        assert leaf.dtype == want, name
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(served))
    assert gauge.value == nbytes < 0.6 * f32_bytes
    sched.params = served
    for a, b in zip(jax.tree.leaves(sched.params), jax.tree.leaves(served)):
        assert a is b
    assert gauge.value == nbytes


def test_engine_keeps_no_float32_weights():
    from repro.serving.inference import ServingEngine

    eng = ServingEngine(ARCH, 0, 32, reduced=True, decode_impl="direct")
    assert eng.params is eng.scheduler.params
    for name, leaf in _named_leaves(eng.params):
        want = jnp.float32 if name in F32_LEAF_NAMES else jnp.bfloat16
        assert leaf.dtype == want, name


# ---------------------------------------------------------------------------
# the stacked cache, updated in place: kernel path against masked einsum
# ---------------------------------------------------------------------------

#: each case differs from ``per_row_pos`` in one way; activations and
#: cache (stored in the activation dtype) are float32 unless a case names
#: another dtype
IN_PLACE_CASES = {
    "scalar_pos": {"per_row": False},
    "per_row_pos": {},
    "bfloat16_cache": {"dtype": "bfloat16"},
    "slot_spliced_mid_run": {"splice_at": 4},
}


def _in_place_steps(case):
    """Prefill two rows, then 8 decode steps fed fixed tokens. Yields, per
    step, the positions, the cache before it and (logits, cache) of the
    kernel path, the masked-einsum path and the float32 masked-einsum
    path, all three from the same cache."""
    opts = {"dtype": "float32", **IN_PLACE_CASES[case]}
    per_row = opts.pop("per_row", True)
    splice_at = opts.pop("splice_at", None)
    cfg = reduced_config(ARCH).replace(attn_kv_block=16, **opts)
    kernel = build(cfg.replace(decode_impl="pallas"))
    params = kernel.init_params(jax.random.PRNGKey(3))
    paths = [jax.jit(build(cfg.replace(decode_impl="direct",
                                       dtype=dt)).decode_fn)
             for dt in (cfg.dtype, "float32")]
    step = jax.jit(kernel.decode_fn, donate_argnums=(1,))
    prefill = jax.jit(make_prefill_step(kernel))
    insert = jax.jit(BatchScheduler._insert_row_impl, donate_argnums=(0,))
    rng = np.random.default_rng(11)
    fed = jnp.asarray(rng.integers(1, 500, (8, 2, 1)), jnp.int32)
    prompts = rng.integers(1, 500, (3, 9))
    lengths = np.asarray([9, 5] if per_row else [9, 9], np.int32)
    cache = kernel.init_cache(2, 32)

    def admit(cache, slot, prompt):
        _, row = prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                         kernel.init_cache(1, 32))
        return insert(cache, row, jnp.asarray(slot, jnp.int32))

    def host(tree):
        return jax.tree.map(lambda t: np.asarray(t, np.float32), tree)

    for slot in range(2):
        cache = admit(cache, slot, prompts[slot, :lengths[slot]])
    for i in range(8):
        if i == splice_at:
            cache = admit(cache, 1, prompts[2, :6])
            lengths[1] = 6 - i
        pos = np.broadcast_to(lengths + i if per_row else lengths[0] + i, (2,))
        arg = jnp.asarray(pos if per_row else pos[0], jnp.int32)
        einsum, einsum32 = (
            host(path(params, jax.tree.map(jnp.asarray, c), fed[i], arg))
            for path, c in zip(paths, (cache, host(cache))))
        before = host(cache)
        logits, cache = step(params, cache, fed[i], arg)
        yield pos, before, host((logits, cache)), einsum, einsum32


@pytest.mark.parametrize("case", list(IN_PLACE_CASES))
def test_in_place_kernel_decode_matches_masked_einsum(case):
    """Prefill, then 8 decode steps in which every layer writes its new
    row into the carried stack in place and the kernel reads its layer
    from the stack, against the masked-einsum path from the same cache at
    the same positions: every entry of the stack but the new rows is left
    exactly as it was, and logits and new rows agree within rounding. In
    float32 that is 1e-5 of the logits' size; in bfloat16 it is how far
    bfloat16 moves the masked-einsum path itself from its float32 run over
    these steps. Cases: one position for all rows or one per row, a
    bfloat16 cache, and a slot spliced in by the scheduler's
    ``_insert_row`` mid-run."""
    steps = list(_in_place_steps(case))
    bf16 = IN_PLACE_CASES[case].get("dtype") == "bfloat16"
    spread = max(np.abs(e[0] - e32[0]).max() for *_, e, e32 in steps)
    for pos, before, (got, got_cache), (want, want_cache), _ in steps:
        tol = spread if bf16 else 1e-5 * np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=tol)
        written = np.zeros((2, 32), bool)
        written[np.arange(2), pos] = True

        def at(leaf, mask):    # (L, B, ..., S) -> (L, positions, ...)
            return np.moveaxis(leaf, -1, 2)[:, mask]

        for name, leaf in got_cache.items():
            np.testing.assert_array_equal(at(leaf, ~written),
                                          at(before[name], ~written))
            new, ref = at(leaf, written), at(want_cache[name], written)
            np.testing.assert_allclose(new, ref, rtol=0,
                                       atol=tol / np.abs(want).max()
                                       * np.abs(ref).max())
