"""Metric ``paged_attn_roofline.serve``: see ``bench.layer_metrics.paged_attn_roofline``."""
from bench.layer_metrics import paged_attn_roofline as read  # noqa: F401
