"""Metric ``queue_wait_p95_s``: see ``bench.layer_metrics.queue_wait_p95_s``."""
from bench.layer_metrics import queue_wait_p95_s as read  # noqa: F401
