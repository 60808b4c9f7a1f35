"""See ``layers.decode_mfu``; the .chat cells."""
from layers import decode_mfu as read  # noqa: F401
