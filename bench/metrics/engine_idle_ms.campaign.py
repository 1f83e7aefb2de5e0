"""Device-idle ms inside a call and outside every decode step, per call
(hits included): process creation, hashing, cache lookup, commits."""
from benchlib.hostspans import engine_idle_ms as read  # noqa: F401
