"""Metric ``device_idle_share.serve``: see ``bench.layer_metrics.device_idle_share``."""
from bench.layer_metrics import device_idle_share as read  # noqa: F401
