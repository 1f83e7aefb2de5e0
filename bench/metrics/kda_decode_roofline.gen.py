"""KDA decode kernel's share of its roofline: the least time the chip
needs for the active requests' state work, over the trace time of the
ops named ``kda_decode`` inside the decode steps. The work of one decode
step of one request is the architecture module's ``decode_state_work``
(each head's float32 state in every KDA layer read and written once, each
element decayed, updated and read out), counted for each traced miss's
decode steps; the least time is its bytes over the HBM peak, or its
operations over the bf16 peak where that is larger. The kernel updates
every slot, so with one live request of four it reads about a quarter.
Reads nothing where no such kernel ran, or the architecture has no such
work."""

from __future__ import annotations

import bisect

from benchlib import readers

KERNEL = "kda_decode"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    work = getattr(run.arch, "decode_state_work", None)
    if work is None:
        return None
    steps = readers._modules(run, readers.DECODE_STEP)
    starts = [s.start for s in steps]
    lo, hi = run.trace_window
    kernel_ns = 0.0
    for op in run.trace.devices[0].ops:
        if not (lo <= op.start and op.end <= hi
                and op.name.lstrip("%").startswith(KERNEL)):
            continue
        # a device runs one program at a time: the step around an op is
        # the last one that began before it
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= steps[i].end:
            kernel_ns += op.dur
    n = sum(c.new_tokens - 1 for c in readers._misses(run))
    if kernel_ns <= 0 or n == 0:
        return None
    ops, nbytes = work(run.model)
    least = n * max(ops / run.peaks["bf16_flops"],
                    nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns * 1e-9)
