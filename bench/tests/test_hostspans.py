"""The readers of device-idle time inside the program's host spans, on a
synthetic trace computed by hand, with the device plane off the host's
clock, and on a recorded trace of a program whose spans did not reach
the profiler."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

import tiny
from benchlib import hostspans, xtrace
from benchlib.spec import Spec

MS = 1e6  # ns
DATA = Path(__file__).resolve().parent / "data" / "decode.xplane.pb"
READERS = ("fetch_idle_ms.gen", "dispatch_idle_ms.gen",
           "step_idle_ms.campaign", "engine_idle_ms.campaign")


def _ev(name, start, end):
    return xtrace.Event(name, start * MS, end * MS)


def _run(host, ops, modules=()):
    host = sorted(host, key=lambda e: (e.start, -e.end))
    ops = sorted(ops, key=lambda e: (e.start, -e.end))
    modules = sorted(modules, key=lambda e: e.start)
    trace = xtrace.Trace([xtrace.Device("/device:TPU:0", modules, ops)],
                         host)
    win = trace.spans("bench.window")[0]
    return types.SimpleNamespace(trace=trace, trace_window=(win.start,
                                                            win.end))


LAUNCH = hostspans.LAUNCH
PROGRAM = [
    _ev("process.create", 10, 60),
    _ev("serving.step", 100, 200),
    _ev("serving.admit", 100, 140),
    _ev(LAUNCH, 146, 150),
    _ev(LAUNCH, 146, 150),       # the runtime nests one in another
    _ev("serving.fetch", 170, 195),
    _ev("serving.step", 210, 300),
    _ev(LAUNCH, 214, 220),
    _ev("serving.fetch", 270, 295),
    _ev("process.create", 600, 650),
    # a call the window cuts: none of its spans is read
    _ev("process.create", 950, 960),
    _ev("serving.step", 960, 1050),
    _ev(LAUNCH, 961, 965),
    _ev("serving.fetch", 1000, 1040),
]
BENCH = [_ev("bench.window", 0, 1000), _ev("bench.call", 10, 500),
         _ev("bench.call", 600, 700), _ev("bench.call", 950, 1100)]
# (op, start, end, step): the decode steps' ops are one program each
OPS = [("create", 20, 50, None), ("prefill", 105, 130, None),
       ("decode", 150, 180, 0), ("decode", 220, 280, 1),
       ("hit", 620, 640, None), ("decode", 965, 1000, 2)]


def _device(shifts):
    """The device's ops and decode programs, each decode step's moved by
    its own clock offset, the rest by the first step's."""
    ops, modules = [], []
    for name, s, e, step in OPS:
        d = shifts[step if step is not None else 0]
        ops.append(_ev(name, s + d, e + d))
        if step is not None:
            modules.append(_ev("jit_serve_step", s + d, e + d))
    return ops, modules


def _read(run):
    spec = Spec(tiny.ROOT)
    return {name: spec.reader(name).read(run) for name in READERS}


# step 1 [100, 200]: busy 25 (prefill) + 30 (decode), idle 45; its
# admission [100, 140] idle 15, its fetch [170, 195] idle 15.
# step 2 [210, 300]: busy 60, idle 30; its fetch [270, 295] idle 15.
# call 1 [10, 500]: busy 30 + 25 + 30 + 60, idle 345, of which the steps
# hold 45 + 30; call 2 [600, 700], a hit: busy 20, idle 80.
BY_HAND = {
    "fetch_idle_ms.gen": (15 + 15) / 2,
    "dispatch_idle_ms.gen": ((45 - 15 - 15) + (30 - 15)) / 2,
    "step_idle_ms.campaign": ((45 - 15) + 30) / 2,
    "engine_idle_ms.campaign": ((345 - 45 - 30) + 80) / 2,
}


@pytest.mark.parametrize("shifts", [
    (0, 0, 0),
    (-9, -9, -9),      # the device plane early, as seen on a v5e
    (6, 6, 6),         # late
    (-9, -7, -8),      # drifting from step to step
], ids=["same-clock", "device-early", "device-late", "drift"])
def test_idle_inside_spans_by_hand(shifts):
    ops, modules = _device(shifts)
    got = _read(_run(PROGRAM + BENCH, ops, modules))
    assert got == pytest.approx(BY_HAND)


def test_uncorrected_split_would_move_with_the_offset():
    # what the correction is for: read on the host's clock alone, a
    # device plane 9 ms early moves idle from the fetch to the dispatch
    ops, modules = _device((-9, -9, -9))
    run = _run(PROGRAM + BENCH, ops, modules)
    idle = hostspans._Idle(run)
    fetches = idle.spans("serving.fetch")
    assert sum(map(idle.of, fetches)) / len(fetches) / MS == \
        pytest.approx((24 + 24) / 2)


def test_launch_offsets_pair_each_launch_with_its_program():
    programs = [100.0, 200.0, 300.0, 400.0]
    assert hostspans.launch_offsets([140.0, 240.0, 340.0], programs) == \
        [-40.0, -40.0, -40.0]
    assert hostspans.launch_offsets([95.0, 197.0], programs) == [5.0, 3.0]
    assert hostspans.launch_offsets([], programs) == []
    assert hostspans.launch_offsets([1.0], []) == []


def test_recorded_trace_planes_disagree():
    # the recorded v5e trace: each decode program starts on the device
    # plane before the host's call that launched it returned
    trace = xtrace.load(str(DATA))
    launches = sorted({e.end for e in trace.host if e.name == LAUNCH})
    programs = sorted(m.start for m in trace.devices[0].modules
                      if m.name == "jit_serve_step")
    offsets = hostspans.launch_offsets(launches, programs)
    paired = {round(t + d) for t, d in zip(launches, offsets)}
    assert len(paired) == len(programs) == 3
    assert all(-2 * MS < d < -0.5 * MS for d in offsets)


def test_no_program_spans_read_none():
    ops, modules = _device((0, 0, 0))
    assert _read(_run(BENCH, ops, modules)) == dict.fromkeys(READERS)


def test_recorded_trace_without_program_spans_reads_none():
    trace = xtrace.load(str(DATA))
    win = trace.spans("bench.window")[0]
    run = types.SimpleNamespace(trace=trace, trace_window=(win.start,
                                                           win.end))
    assert trace.spans("bench.call")
    assert _read(run) == dict.fromkeys(READERS)


def test_untraced_run_reads_none():
    run = types.SimpleNamespace(trace=None, trace_window=None)
    assert _read(run) == dict.fromkeys(READERS)
