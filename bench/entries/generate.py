"""Cells driven through the ``generate`` calcfunction: one client sends
each request as a blocking ``run_get_node(generate, ...)`` on a
file-backed provenance store with caching on, the way a campaign script
drives calcfunctions.

Set-up builds the serving engine that ``generate`` will use, writes the
benchmark's seeded weights into it, and sends one warm-up request per
prompt length. The window then runs the mix for ``seconds``. After it,
every call is checked against the store and the counters, and a sample of
the requests is compared with the plain reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import sys
import time

import jax
import numpy as np

from benchlib import traffic, weights

#: the last seconds of the window that a traced run records at least; the
#: trace starts this long, plus the longest call so far, before the window
#: closes, so that it holds a call however long calls are
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Call:
    prompt_id: int
    prompt: np.ndarray
    prompt_len: int
    new_tokens: int
    t0: float
    t1: float
    pk: int | None
    tokens: np.ndarray | None
    stats: dict | None
    hit: bool = False


@dataclasses.dataclass
class Run:
    model: dict
    #: the configuration's architecture module, ``bench/refs/<reference>.py``
    arch: object
    setup_s: float
    window_s: float
    calls: list[Call]
    counters: dict
    memory_peak_bytes: int
    checks: dict
    failed: int
    #: the sampled requests as the reference sees them: tokens, the
    #: (row, position) pairs of each served token, and the served tokens
    check_batch: tuple | None = None
    #: traced runs only
    spans: list[list[dict]] | None = None
    trace: object | None = None
    trace_window: tuple[float, float] | None = None
    traced_calls: list[Call] | None = None
    peaks: dict | None = None


def program_config(m: dict, ref):
    """The program's configuration for this cell, with the file's
    ``program.overrides`` set on the registered config. Raises where the
    architecture module's ``check_program`` finds it differs from the file,
    or its dtypes differ from those ``program`` states."""
    import importlib

    from repro.configs import _module_name, get_config, reduced_config

    prog = m["program"]
    arch = prog["arch"]
    if prog.get("overrides"):
        mod = importlib.import_module(f"repro.configs.{_module_name(arch)}")
        mod.CONFIG = mod.CONFIG.replace(**prog["overrides"])
    cfg = reduced_config(arch) if prog.get("reduced") else get_config(arch)
    cfg = cfg.replace(decode_impl=prog["decode_impl"])
    wrong = dict(ref.check_program(cfg, m))
    for f in ("dtype", "param_dtype", "kv_cache_dtype"):
        if getattr(cfg, f) != prog[f]:
            wrong[f] = (getattr(cfg, f), prog[f])
    if wrong:
        raise ValueError(f"program config of {arch} differs from the "
                         f"configuration file: {wrong}")
    return cfg


class _CompileCounter:
    """Programs compiled or loaded from the persistent cache."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def _bucket(n: int) -> int:
    max_len = 128
    while max_len < n + 1:
        max_len *= 2
    return max_len


def run(spec, cell: dict, seed: int, seconds: float, trace: bool,
        workdir, t_start: float) -> Run:
    from repro.caching import enable_caching
    from repro.core.datatypes import ArrayData, Bool, Int, Str
    from repro.engine.launch import run_get_node
    from repro.engine.runner import Runner, set_default_runner
    from repro.observability import trace as ptrace
    from repro.observability.metrics import get_registry
    from repro.observability.timeline import load_spans
    from repro.provenance.store import configure_store
    from repro.serving.inference import generate, get_engine, reset_engines

    m = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])
    ref = spec.reference(m["reference"])
    cfg = program_config(m, ref)
    arch, reduced = m["program"]["arch"], bool(m["program"].get("reduced"))
    vocab, eos = m["vocab_size"], int(mix["eos_id"])
    buckets = {_bucket(int(p) + int(mix["new_tokens"]))
               for p in mix["prompt_lengths"]}
    if len(buckets) != 1:
        raise ValueError(f"mix {cell['traffic']} spans cache sizes {buckets}; "
                         "each would build an engine of its own")
    model_seed = int(seed) % 2**31
    counter = _CompileCounter()
    store = configure_store(str(workdir / "profile.db"))
    set_default_runner(Runner(store=store))
    reg = get_registry()

    t = time.monotonic()
    eng = get_engine(arch, model_seed, reduced=reduced,
                     need_len=traffic.longest(mix), eos_id=eos)
    if eng.cfg != cfg:
        raise ValueError(f"engine serves {eng.cfg}, not {cfg}")
    jax.block_until_ready(eng.params)
    phases = {"imports": t - t_start, "engine": time.monotonic() - t}
    t = time.monotonic()
    eng.params = eng.scheduler.params = weights.overwrite(
        eng.params, ref.leaf_specs(m), seed)
    jax.block_until_ready(eng.params)
    phases["weights"] = time.monotonic() - t

    def call(req, annotate) -> Call:
        t0 = time.monotonic()
        ctx = (jax.profiler.TraceAnnotation("bench.call") if annotate
               else contextlib.nullcontext())
        pk = toks = stats = None
        with ctx:
            try:
                res, node = run_get_node(
                    generate.process_class, arch=Str(arch),
                    prompt=ArrayData(req.prompt),
                    max_new_tokens=Int(req.new_tokens), seed=Int(model_seed),
                    eos_id=Int(eos), reduced=Bool(reduced))
                pk = node.pk
                toks = np.asarray(res["tokens"].value)
                stats = dict(res["stats"].value)
            except Exception as exc:  # noqa: BLE001 — a failed call counts
                print(f"call failed: {exc!r}", file=sys.stderr)
        return Call(req.prompt_id, req.prompt, len(req.prompt),
                    req.new_tokens, t0, time.monotonic(), pk, toks, stats)

    with enable_caching():
        t = time.monotonic()
        for req in traffic.warmup(mix, seed, vocab):
            # two tokens compile prefill and the decode step as well as
            # the whole request would, where the cache size is the same
            if _bucket(len(req.prompt) + 2) in buckets:
                req = dataclasses.replace(req, new_tokens=2)
            call(req, False)
        phases["warm-up"] = time.monotonic() - t
        reqs = traffic.requests(mix, seed, vocab)
        gc.collect()
        setup_s = time.monotonic() - t_start
        print("set-up phases (s): " + ", ".join(
            f"{k} {v:.2f}" for k, v in phases.items()), file=sys.stderr)

        # the reduction reads device planes, which only a TPU trace has
        trace_device = trace and jax.devices()[0].platform == "tpu"
        if trace:
            ptrace.enable()
        before = {k: reg.counter(k).value for k in
                  ("cache.hits", "cache.misses", "serving.decode_steps")}
        n0 = counter.n
        calls: list[Call] = []
        tdir = workdir / "trace"
        tracing = window_ann = None
        longest_call = 0.0
        w0 = time.monotonic()
        for req in reqs:
            now = time.monotonic()
            if now - w0 >= seconds:
                break
            left = seconds - (now - w0)
            if trace_device and tracing is None and \
                    left <= TRACE_SECONDS + longest_call:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(tdir), profiler_options=opts)
                window_ann = jax.profiler.TraceAnnotation("bench.window")
                window_ann.__enter__()
                tracing = time.monotonic()
            calls.append(call(req, tracing is not None))
            longest_call = max(longest_call, calls[-1].t1 - calls[-1].t0)
        w1 = time.monotonic()
        if tracing is not None:
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        compiles = counter.n - n0
        counters = {k: reg.counter(k).value - v for k, v in before.items()}
        if trace:
            ptrace.disable()
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    print(f"compilations in window: {compiles}", file=sys.stderr)
    _print_call_times(calls, w0, w1)

    spans = ([load_spans(store, c.pk) for c in calls if c.pk is not None]
             if trace else None)
    checks, failed = _verify(store, calls, counters, m)
    # free the program's device state before the reference runs
    del eng
    reset_engines()
    gc.collect()
    picked = sample(calls, mix, seed)
    batch = reference_batch(picked, mix) if picked else None
    worst = float("inf")
    if batch is not None:
        tokens, rows, served = batch
        worst = gap(ref.logits_at(m, seed, tokens, rows), served)
    checks["logit_gap"] = {"value": worst, "limit": limits["logit_gap"]}
    run = Run(m, ref, setup_s, w1 - w0, calls, counters, peak, checks, failed,
              check_batch=batch, spans=spans)
    if tracing is not None:
        from benchlib import xtrace
        run.trace = xtrace.load(xtrace.find_xplane(str(tdir)))
        win = run.trace.spans("bench.window")[0]
        run.trace_window = (win.start, win.end)
        run.traced_calls = [c for c in calls if c.t0 >= tracing]
    return run


def _print_call_times(calls, w0, w1) -> None:
    """The window's calls by duration, and the host's time between calls,
    so that a slow run shows where its time went."""
    if not calls:
        return
    took = sorted(((c.t1 - c.t0, i, c.prompt_len) for i, c in
                   enumerate(calls)), reverse=True)
    between = (w1 - w0) - sum(d for d, _i, _n in took)
    print(f"calls in window: {len(calls)}, window {w1 - w0:.3f} s, between "
          f"calls {between:.3f} s; slowest (s, index, prompt length): "
          + ", ".join(f"{d:.3f} #{i} {n}" for d, i, n in took[:5]),
          file=sys.stderr)


def _verify(store, calls, counters, m):
    """Exact checks of the engine: every call finished ok with the tokens
    it asked for, the store holds what the call returned, every cache hit
    returned the tokens of the node it was cloned from (a miss, or a hit
    checked the same way), for the same prompt, and the decode steps
    counted are exactly those of the misses."""
    nodes = store.get_nodes([c.pk for c in calls if c.pk is not None],
                            columns=("pk", "exit_status", "attributes"))
    bad = stored_diff = 0
    by_pk = {}
    for c in calls:
        node = nodes.get(c.pk) if c.pk is not None else None
        ok = (node is not None and node["exit_status"] == 0
              and c.tokens is not None and c.tokens.shape == (c.new_tokens,)
              and bool(((c.tokens >= 0) & (c.tokens < m["vocab_size"])).all())
              and c.stats.get("new_tokens") == c.new_tokens
              and c.stats.get("prompt_tokens") == c.prompt_len
              and c.stats.get("finish_reason") == "length")
        if not ok:
            bad += 1
            continue
        attrs = json.loads(node["attributes"] or "{}")
        c.hit = "cached_from_pk" in attrs
        by_pk[c.pk] = c
        out = {label: pk for pk, _t, label in store.outgoing(c.pk)}
        stored = np.asarray(store.load_data(out["tokens"]).value) \
            if "tokens" in out else None
        if stored is None or not np.array_equal(stored, c.tokens):
            stored_diff += 1
    hit_diff = 0
    for c in by_pk.values():
        if c.hit:
            src = by_pk.get(json.loads(nodes[c.pk]["attributes"])
                            ["cached_from_pk"])
            if src is None or src.prompt_id != c.prompt_id or \
                    not np.array_equal(src.tokens, c.tokens):
                hit_diff += 1
    steps = sum(c.new_tokens - 1 for c in by_pk.values() if not c.hit)
    checks = {
        "calls_not_ok": {"value": bad, "limit": 0},
        "stored_differs": {"value": stored_diff, "limit": 0},
        "hit_differs": {"value": hit_diff, "limit": 0},
        "decode_steps_off": {"value": abs(counters["serving.decode_steps"]
                                          - steps), "limit": 0},
    }
    return checks, bad


def sample(calls, mix, seed) -> list[Call]:
    """Finished misses to compare with the reference, drawn from the seed,
    with a longest one among them."""
    misses = {}
    for c in calls:
        if c.tokens is not None and not c.hit:
            misses.setdefault(c.prompt_id, c)
    pool = list(misses.values())
    if not pool:
        return []
    longest = max(pool, key=lambda c: c.prompt_len)
    rest = [c for c in pool if c is not longest]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 3])
    k = min(len(rest), int(mix["check_requests"]) - 1)
    picked = [rest[i] for i in rng.choice(len(rest), size=k, replace=False)]
    return [longest, *picked]


def reference_batch(picked, mix):
    """Token rows (padded to the mix's longest sequence, so one program
    serves every run) and the (row, position) pairs whose logits chose
    each served token."""
    width = traffic.longest(mix) - 1
    tokens = np.zeros((len(picked), width), np.int32)
    rows, served = [], []
    for i, c in enumerate(picked):
        seq = np.concatenate([np.asarray(c.prompt, np.int32),
                              c.tokens[:-1]])
        tokens[i, :len(seq)] = seq
        rows += [(i, c.prompt_len - 1 + j) for j in range(c.new_tokens)]
        served += list(c.tokens)
    return tokens, np.asarray(rows, np.int32), np.asarray(served)


def gap(ref_logits: np.ndarray, chosen: np.ndarray) -> float:
    """Widest amount by which a chosen token's reference logit lies below
    the reference's best at that position."""
    best = ref_logits.max(axis=-1)
    return float((best - ref_logits[np.arange(len(chosen)), chosen]).max())
