"""See ``layers.paged_attn_roofline``; the .chat cells."""
from layers import paged_attn_roofline as read  # noqa: F401
