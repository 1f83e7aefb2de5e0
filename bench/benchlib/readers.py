"""Arithmetic shared by the metric readers in ``bench/metrics/``. Each
reader returns ``None`` when its run holds nothing to read. Useful work
is counted by the run's architecture module (``run.arch``)."""

from __future__ import annotations

import bisect
import math

from benchlib import xtrace

DECODE_STEP = "jit_serve_step"
KERNEL = 'custom_call_target="tpu_custom_call"'


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def idle_share(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = [xtrace.busy_ns(d, lo, hi) for d in run.trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def _modules(run, name):
    lo, hi = run.trace_window
    return [e for e in run.trace.devices[0].modules
            if e.name == name and e.start >= lo and e.end <= hi]


def decode_step_ms(run):
    if run.trace is None:
        return None
    steps = _modules(run, DECODE_STEP)
    if not steps:
        return None
    return sum(e.dur for e in steps) / len(steps) * 1e-6


def step_gap_ms(run):
    """Mean device-idle time between consecutive decode steps of one
    call."""
    if run.trace is None:
        return None
    busy = xtrace.BusyIndex(run.trace.devices[0])
    steps = _modules(run, DECODE_STEP)
    calls = run.trace.spans("bench.call")
    gaps = []
    for a, b in zip(steps, steps[1:]):
        if not any(c.start <= a.start and b.end <= c.end for c in calls):
            continue
        gaps.append((b.start - a.end) - busy.between(a.end, b.start))
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None


def _misses(run):
    return [c for c in (run.traced_calls or [])
            if c.tokens is not None and not c.hit]


def decode_attn_roofline(run):
    """Least time the chip needs for the decode-attention kernel's useful
    work (the active request's cache in every layer that holds attention,
    as the architecture module counts it), over the kernel's time in the
    trace. Decode attention reads every cached byte once for a few
    operations per byte, so the byte bound is the larger one at these
    shapes."""
    if run.trace is None or run.peaks is None:
        return None
    steps = _modules(run, DECODE_STEP)
    starts = [s.start for s in steps]
    lo, hi = run.trace_window
    kernel_ns = 0.0
    for op in run.trace.devices[0].ops:
        if not (lo <= op.start and op.end <= hi and KERNEL in op.name):
            continue
        # a device runs one program at a time: the step around an op is
        # the last one that began before it
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= steps[i].end:
            kernel_ns += op.dur
    ops = nbytes = 0
    for c in _misses(run):
        for i in range(c.new_tokens - 1):
            o, b = run.arch.decode_attention_work(run.model,
                                                  c.prompt_len + 1 + i)
            ops += o
            nbytes += b
    if kernel_ns <= 0 or ops == 0:
        return None
    least = max(ops / run.peaks["bf16_flops"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns * 1e-9)


def mfu(run):
    """Model operations of every prefill and decode step in the traced
    window, over the window, over the chip's bf16 peak."""
    if run.trace is None or run.peaks is None:
        return None
    work = sum(run.arch.generate_flops(run.model, c.prompt_len, c.new_tokens)
               for c in _misses(run))
    if not work:
        return None
    lo, hi = run.trace_window
    return 100.0 * work / ((hi - lo) * 1e-9) / run.peaks["bf16_flops"]
