"""Device-idle ms inside the scheduler's token fetch (``serving.fetch``),
per decode step (``serving.step``) that holds one."""
from benchlib.hostspans import fetch_idle_ms as read  # noqa: F401
