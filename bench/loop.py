"""The open loop: lead-in, measured window, drain.

Adapted from ``repro.serving.gateway.drive_open_loop``, changed so that
every request keeps the time it was due (latency counts from there, so a
stall shows in the requests behind it), the loop has a window and a drain
cap, and the generator's lateness is recorded.

Each request of the mix's Poisson schedule is submitted when it is due.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import ExitStack
from dataclasses import dataclass, field
from typing import List, Optional

from jax.profiler import TraceAnnotation

from bench import traffic
from repro.serving.gateway import ServeRequest

IDLE_SLEEP_S = 0.002


@dataclass
class Sent:
    req: traffic.Req
    due: float              # clock time the request was due
    submitted: float        # clock time it was submitted
    handle: object
    t_last: float = -1.0    # clock time of its last delivered token


@dataclass
class RunLog:
    sent: List[Sent]
    t_start: float
    due0: float             # requests due in [due0, due1) are the window's
    due1: float
    t0: float               # first pump boundary at or after due0
    t1: float               # first pump boundary at or after due1
    t_cap: float
    t_end: float
    tokens_t0: int          # output tokens delivered by t0
    tokens_t1: int          # output tokens delivered by t1
    compiles_t0: int = 0
    compiles_t1: int = 0
    trace_t0: float = -1.0
    trace_t1: float = -1.0

    def in_window(self) -> List[Sent]:
        return [s for s in self.sent if self.due0 <= s.due < self.due1]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


@dataclass
class _Tracer:
    """Profiler trace, with a ``bench.window`` span over what it covers."""
    log_dir: Optional[str]
    stack: ExitStack = field(default_factory=ExitStack)
    on: bool = False

    def start(self):
        import jax
        jax.profiler.start_trace(self.log_dir)
        self.stack.enter_context(TraceAnnotation("bench.window"))
        self.on = True

    def stop(self):
        import jax
        if self.on:
            self.stack.close()
            jax.profiler.stop_trace()
            self.on = False


def drive(dep, mix: dict, seed: int, window_s: float, vocab: int, *,
          compiles=lambda: 0, trace_dir: Optional[str] = None,
          trace_s: float = 0.0) -> RunLog:
    gw, clock = dep.gateway, dep.clock
    pending = deque(traffic.poisson_schedule(mix, seed, window_s, vocab))
    sent: List[Sent] = []
    by_handle = {}
    delivered = [0]

    def on_token(h, _tok):
        by_handle[id(h)].t_last = clock()
        delivered[0] += 1

    def submit(r: traffic.Req, due: float):
        h = gw.submit(ServeRequest(r.rid, r.tokens, max_new_tokens=r.n_out),
                      on_token=on_token)
        s = Sent(r, due, clock(), h)
        by_handle[id(h)] = s
        sent.append(s)

    tracer = _Tracer(trace_dir)
    gw.heartbeat_all()
    t_start = clock()
    t0_due = t_start + float(mix["lead_in_s"])
    t1_due = t0_due + window_s
    t_cap = t1_due + float(mix["drain_cap_s"])
    marks = {}
    try:
        while True:
            now = clock()
            if "t0" not in marks and now >= t0_due:
                marks.update(t0=now, tok0=delivered[0], c0=compiles())
                if trace_dir is not None:
                    tracer.start()
                    marks["tr0"] = clock()
            if tracer.on and now >= marks["t0"] + trace_s:
                marks["tr1"] = clock()
                tracer.stop()
            if "t0" in marks and "t1" not in marks and now >= t1_due:
                marks.update(t1=now, tok1=delivered[0], c1=compiles())
            while pending and t_start + pending[0].due <= now:
                r = pending.popleft()
                submit(r, t_start + r.due)
            if "t1" in marks:
                due_open = [s for s in sent if t0_due <= s.due < t1_due
                            and not s.handle.is_terminal]
                if not due_open or now >= t_cap:
                    break
            busy = (gw.queue or gw.transfer_queue or gw.retry_queue
                    or any(d.client.active for d in gw.dec))
            if busy:
                with TraceAnnotation("bench.pump"):
                    gw.pump()
            else:
                nxt = (t_start + pending[0].due - now) if pending \
                    else IDLE_SLEEP_S
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(min(nxt, IDLE_SLEEP_S), 0.0))
    finally:
        if tracer.on:
            marks["tr1"] = clock()
            tracer.stop()
    t_end = clock()
    return RunLog(sent=sent, t_start=t_start, due0=t0_due, due1=t1_due,
                  t0=marks["t0"], t1=marks["t1"],
                  t_cap=t_cap, t_end=t_end, tokens_t0=marks["tok0"],
                  tokens_t1=marks["tok1"], compiles_t0=marks["c0"],
                  compiles_t1=marks["c1"], trace_t0=marks.get("tr0", -1.0),
                  trace_t1=marks.get("tr1", -1.0))


def cancel_open(dep, log: RunLog) -> int:
    """Cancel what is still in flight, so its slots and pages are freed."""
    n = 0
    for s in log.sent:
        if not s.handle.is_terminal:
            s.handle.cancel()
            n += 1
    return n
