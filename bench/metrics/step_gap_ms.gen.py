"""Mean device-idle time between consecutive decode steps of a call."""
from benchlib.readers import step_gap_ms as read  # noqa: F401
