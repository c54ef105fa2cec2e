"""Metric ``queue_behind_decode_p95_s`` (gateway): per request due in the
traced window, the part of its wait in the gateway's queue (due time to
leaving QUEUED, as ``queue_wait_p95_s`` reads it) during which a decode
chunk ran; 95th percentile. A request still queued at the drain cap
waits to the cap.

Decode chunks are the decode seam's host spans (``SeamLog.chunks``),
the same intervals as the program's ``serve.decode_chunk`` spans, on
the clock the gateway runs on. Read in the traced run; None without a
trace or without a request in the window."""
from typing import Optional

from bench import stats


def read(run) -> Optional[float]:
    if run.trace is None:
        return None
    chunks = [(c.t0, c.t1) for c in run.seams.chunks]
    behind = []
    for s in run.log.in_window():
        lo = s.due
        hi = s.due + stats.queue_wait(s, run.log.t_cap)
        behind.append(sum(max(0.0, min(hi, b) - max(lo, a))
                          for a, b in chunks))
    return stats.percentile(behind, 95)
