"""Window arithmetic: per-request latencies from the due time, tails, and
tokens delivered in the window.

The arithmetic of ``repro.serving.gateway.summarize_handles`` and
``RequestHandle.ttft/tpot``, changed to time from when a request was due
instead of when it was submitted, so a stalled generator or gateway shows.
A request with no first token when the drain cap ends the run has failed;
its time to first token is counted as at least the cap minus its due time.
One that has tokens counts with the tokens it has.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default), or None if empty."""
    xs = [x for x in xs if not math.isnan(x)]
    return float(np.percentile(xs, q)) if xs else None


def has_first(s) -> bool:
    return s.handle.t_first > 0 and len(s.handle.tokens) > 0


def failed(log) -> List:
    """Requests due in the window that never got a first token."""
    return [s for s in log.in_window() if not has_first(s)]


def ttft(s, t_cap: float) -> float:
    """Due time to first token; for a request without one, the cap."""
    if has_first(s):
        return s.handle.t_first - s.due
    return max(t_cap, s.due) - s.due


def tpot(s) -> float:
    """Mean gap between the output tokens the request received, or NaN
    with fewer than two."""
    n = len(s.handle.tokens)
    if n < 2 or s.t_last < 0:
        return math.nan
    return (s.t_last - s.handle.t_first) / (n - 1)


def queue_wait(s, t_cap: float) -> float:
    """Due time to leaving QUEUED (the gateway's own state history)."""
    for t, state in s.handle.history[1:]:
        if state != "QUEUED":
            return t - s.due
    return max(t_cap, s.due) - s.due


def ttfts(log) -> List[float]:
    return [ttft(s, log.t_cap) for s in log.in_window()]


def tpots(log) -> List[float]:
    return [tpot(s) for s in log.in_window()]


def tokens_per_s(log) -> float:
    """Output tokens delivered between the window's pump boundaries, over
    the time between them."""
    return (log.tokens_t1 - log.tokens_t0) / (log.t1 - log.t0)


def lateness(log) -> List[float]:
    """Submission time minus due time, per request of the window."""
    return [s.submitted - s.due for s in log.in_window()]
