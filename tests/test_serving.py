"""Continuous-batching scheduler, prefill/decode parity, sharded-serving
equivalence, and the provenance-cached generate() workload.

Everything runs on CPU: the Pallas decode path executes in interpret mode,
and the multi-device test forces fake host devices in a subprocess (the
main pytest process keeps its single CPU device).
"""

import json
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
        dtype="float32", kv_cache_dtype="float32",
        decode_impl=decode_impl, **over)
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
# prefill/decode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["aiida-demo-110m", "recurrentgemma-2b"])
def test_prefill_equals_stepwise_decode(arch):
    """Prefilling N tokens must land in the same state as feeding those N
    tokens one decode step at a time: identical next token and identical
    greedy continuation."""
    cfg = reduced_config(arch).replace(dtype="float32",
                                       kv_cache_dtype="float32")
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
        dtype="float32", kv_cache_dtype="float32", decode_impl="pallas")
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
