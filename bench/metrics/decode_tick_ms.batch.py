"""See ``layers.decode_tick_ms``; the .batch cells."""
from layers import decode_tick_ms as read  # noqa: F401
