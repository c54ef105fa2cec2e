"""Metric ``setup_s``: see ``bench.layer_metrics.setup_s``."""
from bench.layer_metrics import setup_s as read  # noqa: F401
