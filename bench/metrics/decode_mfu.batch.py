"""See ``layers.decode_mfu``; the .batch cells."""
from layers import decode_mfu as read  # noqa: F401
