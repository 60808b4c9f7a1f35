"""See ``layers.paged_attn_roofline``; the .batch cells."""
from layers import paged_attn_roofline as read  # noqa: F401
