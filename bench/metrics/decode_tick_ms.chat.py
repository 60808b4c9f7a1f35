"""See ``layers.decode_tick_ms``; the .chat cells."""
from layers import decode_tick_ms as read  # noqa: F401
