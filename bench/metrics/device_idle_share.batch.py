"""See ``layers.device_idle_share``; the .batch cells."""
from layers import device_idle_share as read  # noqa: F401
