"""What a metric reader sees of one run (``bench/metrics/<name>.py``)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

from bench.deploy import ChunkLog, PrefillLog, SeamLog
from bench.loop import RunLog
from bench.trace_reduce import Reduced


@dataclass
class Run:
    log: RunLog                 # requests, due times, window marks
    seams: SeamLog              # decode chunks and prefills, host clock
    dims: Dict[str, int]        # L, d, H, Hkv, hd, f, V
    group: int                  # int4 quantization group of the KV pages
    max_slots: int
    peaks: Dict[str, float]     # of this device kind (bench/peaks.json)
    setup_s: float
    trace: Optional[Reduced]    # None unless --trace 1

    def _traced(self, t0: float, t1: float) -> bool:
        return (self.trace is not None and self.log.trace_t0 <= t0
                and t1 <= self.log.trace_t1)

    def traced_chunks(self) -> List[ChunkLog]:
        """Decode chunks that ran wholly inside the traced window."""
        return [c for c in self.seams.chunks if self._traced(c.t0, c.t1)]

    def traced_prefills(self) -> List[PrefillLog]:
        return [p for p in self.seams.prefills if self._traced(p.t0, p.t1)]
