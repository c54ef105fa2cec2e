"""Metric ``prefill_mfu``: see ``bench.layer_metrics.prefill_mfu``."""
from bench.layer_metrics import prefill_mfu as read  # noqa: F401
