"""Model FLOPs of all prefill and decode in the traced window, over the
window and the chip's bf16 peak."""
from benchlib.readers import mfu as read  # noqa: F401
