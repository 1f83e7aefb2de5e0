"""Device-idle ms inside a decode step (``serving.step``) outside its
token fetch and admissions: bookkeeping, input transfer, dispatch."""
from benchlib.hostspans import dispatch_idle_ms as read  # noqa: F401
