"""Mean device time of one decode step (the serving scheduler's jitted
step, all layers)."""
from benchlib.readers import decode_step_ms as read  # noqa: F401
