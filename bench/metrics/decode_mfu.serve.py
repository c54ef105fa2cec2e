"""Metric ``decode_mfu.serve``: see ``bench.layer_metrics.decode_mfu``."""
from bench.layer_metrics import decode_mfu as read  # noqa: F401
