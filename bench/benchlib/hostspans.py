"""Device-idle time inside the program's own host spans.

The program's spans (``repro.observability.trace``) enter a profiler
annotation of the same name on the thread that drives the call, the one
that also holds ``bench.call``, so ``xtrace.load`` keeps them in
``trace.host``. Only spans that lie wholly inside ``bench.window`` are
read, and idle time is that of the first device.

The host and device planes of one trace are not on quite the same clock:
on a v5e the device plane has been seen up to about 1.5 ms off the host's,
by a different amount in each run. A decode step's split between its
fetch and the rest hinges on that offset, so each ``serving.step`` is read
on its own device clock: the decode program it launches (``jit_serve_step``)
is taken to start when the host's call that launched it
(``PjitFunction(serve_step)``) returns. The time from that return to the
program's real start (about 0.1 ms where the planes agree) is then charged
to the fetch.

Names read here (the program's contract): ``serving.step`` (one decode
step of the scheduler, with its admissions), ``serving.fetch`` (the
step's token fetch), ``serving.admit`` (one request's prefill and first
token) and ``process.create`` (an engine process being created). A
trace without them, such as one of a program that has no such spans,
reads ``None``.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics

from benchlib import xtrace
from benchlib.readers import DECODE_STEP

STEP, FETCH, ADMIT = "serving.step", "serving.fetch", "serving.admit"
CREATE, CALL = "process.create", "bench.call"
#: the host event of the call that launches ``DECODE_STEP``
LAUNCH = "PjitFunction(serve_step)"


@dataclasses.dataclass
class Step:
    span: xtrace.Event   # the serving.step, on the host's clock
    idle: float          # ns, the whole step
    fetch_idle: float    # ns, inside its serving.fetch
    admit_idle: float    # ns, inside its serving.admit spans
    fetches: int


class _Idle:
    """Device-idle time inside host spans, from one merge of the ops."""

    def __init__(self, run):
        self.lo, self.hi = run.trace_window
        self.busy = xtrace.BusyIndex(run.trace.devices[0])
        self.host = run.trace.host

    def spans(self, name: str) -> list[xtrace.Event]:
        return [e for e in self.host if e.name == name
                and self.lo <= e.start and e.end <= self.hi]

    def of(self, e: xtrace.Event, offset: float = 0.0) -> float:
        """Idle ns inside ``e``, read ``offset`` ns later on the device."""
        return e.dur - self.busy.between(e.start + offset, e.end + offset)


def _inside(outer: xtrace.Event, inner: list[xtrace.Event],
            starts: list[float]) -> list[xtrace.Event]:
    """The events of ``inner`` (sorted by start) that lie within ``outer``."""
    i = bisect.bisect_left(starts, outer.start)
    j = bisect.bisect_right(starts, outer.end)
    return [e for e in inner[i:j] if e.end <= outer.end]


def _nearest(starts: list[float], t: float) -> float:
    i = bisect.bisect_left(starts, t)
    return min(starts[max(0, i - 1):i + 1], key=lambda s: abs(s - t))


def launch_offsets(launches: list[float],
                   programs: list[float]) -> list[float]:
    """Device minus host clock at each launch: the start of the program
    it launched less the host time the launching call returned. Both
    lists ascend. Each launch is paired with the program nearest to it
    once the run's median offset is added, so an offset of up to about
    half a step pairs right."""
    if not launches or not programs:
        return []
    first = statistics.median(_nearest(programs, t) - t for t in launches)
    return [_nearest(programs, t + first) - t for t in launches]


def steps(run) -> list[Step] | None:
    """Every ``serving.step`` in the window with the idle time inside it
    and inside its fetch and admissions, each step on its own device
    clock; None without a trace or steps."""
    if run.trace is None:
        return None
    idle = _Idle(run)
    found = idle.spans(STEP)
    if not found:
        return None
    fetches, admits = idle.spans(FETCH), idle.spans(ADMIT)
    f_starts = [e.start for e in fetches]
    a_starts = [e.start for e in admits]
    calls = idle.spans(LAUNCH)
    c_starts = [e.start for e in calls]
    held = []
    for s in found:
        f = _inside(s, fetches, f_starts)
        # the step's decode launch: the last one that returns before
        # its fetch (a launch event may be nested in one of its name)
        launched = [e.end for e in _inside(s, calls, c_starts)
                    if f and e.end <= f[0].start]
        held.append((s, f, max(launched) if launched else None))
    programs = sorted(m.start for m in run.trace.devices[0].modules
                      if m.name == DECODE_STEP)
    known = [t for _s, _f, t in held if t is not None]
    offsets = dict(zip(known, launch_offsets(known, programs)))
    rest = statistics.median(offsets.values()) if offsets else 0.0
    out = []
    for s, f, t in held:
        off = offsets.get(t, rest)
        a = _inside(s, admits, a_starts)
        out.append(Step(s, idle.of(s, off),
                        sum(idle.of(e, off) for e in f),
                        sum(idle.of(e, off) for e in a), len(f)))
    return out


def fetch_idle_ms(run):
    """Device-idle ms inside ``serving.fetch``, per step that holds one."""
    held = [s for s in steps(run) or [] if s.fetches]
    return sum(s.fetch_idle for s in held) / len(held) * 1e-6 \
        if held else None


def dispatch_idle_ms(run):
    """Device-idle ms inside ``serving.step``, less that inside its fetch
    and admissions, per step that holds a fetch."""
    held = [s for s in steps(run) or [] if s.fetches]
    return sum(s.idle - s.fetch_idle - s.admit_idle for s in held) \
        / len(held) * 1e-6 if held else None


def step_idle_ms(run):
    """Device-idle ms inside ``serving.step``, less that inside its
    admissions, per step."""
    found = steps(run)
    return sum(s.idle - s.admit_idle for s in found) / len(found) * 1e-6 \
        if found else None


def engine_idle_ms(run):
    """Device-idle ms inside ``bench.call`` and outside every
    ``serving.step`` (each read on its own device clock), per call, hits
    included; None where the trace holds no ``process.create``, so no
    engine span reached the profiler."""
    if run.trace is None:
        return None
    idle = _Idle(run)
    calls = idle.spans(CALL)
    if not calls or not idle.spans(CREATE):
        return None
    found = steps(run) or []
    starts = [s.span.start for s in found]
    total = 0.0
    for c in calls:
        i = bisect.bisect_left(starts, c.start)
        j = bisect.bisect_right(starts, c.end)
        total += idle.of(c) - sum(s.idle for s in found[i:j]
                                  if s.span.end <= c.end)
    return total / len(calls) * 1e-6
