"""95th percentile of every call's time, from the call to its finished
node."""
from benchlib.readers import p95


def read(run):
    return p95([(c.t1 - c.t0) * 1e3 for c in run.calls]) if run.calls else None
