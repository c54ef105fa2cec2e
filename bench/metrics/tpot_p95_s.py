"""Metric ``tpot_p95_s``: see ``bench.layer_metrics.tpot_p95_s``."""
from bench.layer_metrics import tpot_p95_s as read  # noqa: F401
