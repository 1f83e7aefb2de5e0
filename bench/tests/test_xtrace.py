"""The trace reduction, checked on a small recorded TPU trace
(``record_trace.py``: one qwen2-0.5b call, a 32-token prompt, 4 new
tokens) against plain recomputations from the raw events."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import readers, xtrace

DATA = Path(__file__).resolve().parent / "data" / "decode.xplane.pb"
LAYERS = 24


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(str(DATA))


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    plane = ProfileData.from_file(str(DATA)).find_plane_with_name(
        "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    return lines["XLA Ops"], lines["XLA Modules"]


def _window(trace):
    w = trace.spans("bench.window")
    assert len(w) == 1
    return w[0].start, w[0].end


def test_programs_of_one_call(trace):
    names = [m.name for m in trace.devices[0].modules]
    assert names.count("jit_prefill_step") == 1
    assert names.count(readers.DECODE_STEP) == 3       # NEW - 1 steps
    assert len(trace.spans("bench.call")) == 1


def test_busy_is_the_union_of_op_intervals(trace, raw):
    lo, hi = _window(trace)
    ops, _ = raw
    # sweep over every op boundary, independent of xtrace.merged
    edges = sorted([(e.start_ns, 1) for e in ops] +
                   [(e.start_ns + e.duration_ns, -1) for e in ops])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        t = min(max(t, lo), hi)
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    got = xtrace.busy_ns(trace.devices[0], lo, hi)
    assert got == pytest.approx(busy, rel=1e-9)
    gaps = xtrace.idle_gaps(trace.devices[0], lo, hi)
    assert got + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    assert 0 < got < hi - lo


def test_decode_step_time_and_kernel_count(trace, raw):
    _, mods = raw
    steps = [e for e in mods if e.name.startswith(readers.DECODE_STEP + "(")]
    want = sum(e.duration_ns for e in steps) / len(steps) * 1e-6
    run = types.SimpleNamespace(trace=trace, trace_window=_window(trace))
    assert readers.decode_step_ms(run) == pytest.approx(want)
    kernels = [o for o in trace.devices[0].ops if readers.KERNEL in o.name]
    assert len(kernels) == LAYERS * len(steps)
    gap = readers.step_gap_ms(run)
    assert gap is not None and gap >= 0


def test_idle_share_and_breakdown(trace):
    lo, hi = _window(trace)
    run = types.SimpleNamespace(trace=trace, trace_window=(lo, hi))
    busy = xtrace.busy_ns(trace.devices[0], lo, hi)
    assert readers.idle_share(run) == pytest.approx(
        100 * (1 - busy / (hi - lo)))
    b = xtrace.breakdown(trace, lo, hi)
    ops, idle = b["device_ops"], b["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(idle) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert sum(v for _, v in ops) <= busy * 1e-9 * (1 + 1e-9)
    assert sum(v for _, v in idle) <= (hi - lo - busy) * 1e-9 * (1 + 1e-9)


def test_indexed_busy_time_and_host_labels_match_plain_scans(trace):
    """The one-merge busy index and the one-sweep host labels give what a
    fresh merge and a plain scan of the host events give."""
    dev = trace.devices[0]
    lo, hi = _window(trace)
    index = xtrace.BusyIndex(dev)
    edges = sorted({o.start for o in dev.ops} | {o.end for o in dev.ops})
    cuts = [lo, hi, *edges[::7], *((a + b) / 2 for a, b in
                                   zip(edges[::11], edges[1::11]))]
    for a, b in zip(cuts, cuts[3:]):
        a, b = min(a, b), max(a, b)
        assert index.between(a, b) == pytest.approx(
            xtrace.busy_ns(dev, a, b), abs=1e-3)

    def plain(t):
        best = None
        for e in trace.host:
            if e.start <= t <= e.end and (best is None or e.dur < best.dur):
                best = e
        return best.name if best is not None else "host: no traced event"

    times = sorted((s + e) / 2 for s, e in xtrace.idle_gaps(dev, lo, hi))
    times += [trace.host[0].start - 1.0, trace.host[-1].end + 1.0]
    times.sort()
    assert xtrace.host_labels(trace, times) == [plain(t) for t in times]


def test_op_labels():
    text = ('%convert.21 = bf16[24,896,4864]{2,1,0} convert(f32[24,896,4864]'
            '{2,1,0} %params)')
    assert xtrace.op_label(text) == "convert.21 convert"
    kernel = ('%closed_call.8 = bf16[4,2,7,64]{3,2,1,0} custom-call(s32[4] '
              '%a), custom_call_target="tpu_custom_call"')
    assert xtrace.op_label(kernel) == "closed_call.8 custom-call:tpu_custom_call"
    assert xtrace.module_name("jit_serve_step(1492737)") == "jit_serve_step"
