"""Decode-attention kernel's share of its roofline (byte-bound)."""
from benchlib.readers import decode_attn_roofline as read  # noqa: F401
