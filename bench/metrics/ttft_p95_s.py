"""Metric ``ttft_p95_s``: see ``bench.layer_metrics.ttft_p95_s``."""
from bench.layer_metrics import ttft_p95_s as read  # noqa: F401
