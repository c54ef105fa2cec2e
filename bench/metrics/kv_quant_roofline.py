"""Metric ``kv_quant_roofline``: see ``bench.layer_metrics.kv_quant_roofline``."""
from bench.layer_metrics import kv_quant_roofline as read  # noqa: F401
