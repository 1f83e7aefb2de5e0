"""Device-idle ms inside a decode step (``serving.step``) outside its
admissions, per step."""
from benchlib.hostspans import step_idle_ms as read  # noqa: F401
