"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The device planes (``/device:TPU:<n>``) carry two lines that matter: XLA
Modules (one event per program run, named ``jit_<fn>(<hash>)``) and XLA
Ops (one event per operation, named by its HLO text; operations inside a
loop nest inside the loop's own event). The host plane carries the
benchmark's ``jax.profiler.TraceAnnotation`` spans and the runtime's own
host events on the same clock.

Busy time is the union of operation intervals; idle is the rest of the
window. Self time of an operation is its duration less that of the
operations nested in it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import itertools
import os
import re

from jax.profiler import ProfileData

_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclasses.dataclass
class Event:
    name: str
    start: float     # ns, trace clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: list[Event]
    ops: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    #: the host line that holds the benchmark's annotations
    host: list[Event]

    def spans(self, name: str) -> list[Event]:
        return [e for e in self.host if e.name == name]


def module_name(name: str) -> str:
    """``jit_serve_step(1492...)`` -> ``jit_serve_step``."""
    return name.split("(", 1)[0]


def op_label(text: str) -> str:
    """A short, stable label for an op's HLO text."""
    head, _, rest = text.lstrip("%").partition(" = ")
    target = _TARGET.search(rest)
    if target:
        return f"{head} custom-call:{target.group(1)}"
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1) if m else '?'}"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, annotation_prefix: str = "bench.") -> Trace:
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines["XLA Ops"].events]
            mods = [Event(module_name(e.name), e.start_ns,
                          e.start_ns + e.duration_ns)
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            if ops:
                ops.sort(key=lambda e: (e.start, -e.end))
                mods.sort(key=lambda e: e.start)
                devices.append(Device(plane.name, mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if not e.name.startswith("$")]
                if any(e.name.startswith(annotation_prefix) for e in evs):
                    host.extend(evs)
    if not devices:
        raise ValueError(f"{path}: no device plane with operations")
    host.sort(key=lambda e: (e.start, -e.end))
    return Trace(devices, host)


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(((o.start, o.end) for o in dev.ops),
                                        lo, hi))


class BusyIndex:
    """Busy time of a device between any two times, from one merge of
    its operations: ``between(s, e)`` equals ``busy_ns(dev, s, e)``."""

    def __init__(self, dev: Device):
        self.spans = merged(((o.start, o.end) for o in dev.ops),
                            float("-inf"), float("inf"))
        self.starts = [s for s, _e in self.spans]
        self.before = [0.0, *itertools.accumulate(
            e - s for s, e in self.spans)]

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.spans[i - 1]
        return self.before[i - 1] + min(e, t) - s

    def between(self, s: float, e: float) -> float:
        return max(0.0, self._upto(e) - self._upto(s))


def idle_gaps(dev: Device, lo: float, hi: float) -> list[tuple[float, float]]:
    busy = merged(((o.start, o.end) for o in dev.ops), lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def self_times(dev: Device, lo: float, hi: float) -> dict[str, float]:
    """Self time (ns) per op label, with the module it ran in."""
    starts = [m.start for m in dev.modules]
    out: dict[str, float] = {}
    stack: list[tuple[Event, str]] = []
    for op in dev.ops:
        if op.start < lo or op.end > hi:
            continue
        while stack and stack[-1][0].end <= op.start:
            stack.pop()
        i = bisect.bisect_right(starts, op.start) - 1
        mod = dev.modules[i].name if i >= 0 and \
            dev.modules[i].end >= op.start else "?"
        label = f"{mod}/{op_label(op.name)}"
        out[label] = out.get(label, 0.0) + op.dur
        if stack:
            parent = stack[-1][1]
            out[parent] -= op.dur
        stack.append((op, label))
    return out


def host_labels(trace: Trace, times: list[float]) -> list[str]:
    """The innermost (shortest) host event around each of the ascending
    ``times``; of equally short ones, the one that starts first. One
    sweep over the host events, whatever the number of times."""
    out, active, i = [], [], 0
    host = trace.host
    for t in times:
        while i < len(host) and host[i].start <= t:
            heapq.heappush(active, (host[i].dur, i))
            i += 1
        # an event that ended before t has ended before every later time
        while active and host[active[0][1]].end < t:
            heapq.heappop(active)
        out.append(host[active[0][1]].name if active
                   else "host: no traced event")
    return out


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Device ops with the most self time, and idle time by what the host
    was doing, both in seconds, for the first device."""
    dev = trace.devices[0]
    ops = sorted(self_times(dev, lo, hi).items(), key=lambda kv: -kv[1])
    idle: dict[str, float] = {}
    gaps = idle_gaps(dev, lo, hi)
    labels = host_labels(trace, [(s + e) / 2 for s, e in gaps])
    for (s, e), label in zip(gaps, labels):
        idle[label] = idle.get(label, 0.0) + (e - s)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v * 1e-9] for k, v in ops[:top]],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps[:top]]}
