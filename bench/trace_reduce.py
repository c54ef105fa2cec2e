"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-program and per-kernel device time, and idle gaps by host span.

``load`` reads the file with ``jax.profiler.ProfileData``; everything
after it works on plain event lists, so it is tested on a small recorded
trace without a chip.

* Device events: the ``XLA Ops`` line (one event per operation) and the
  ``XLA Modules`` line (one event per program execution) of every
  ``/device:`` plane. An operation is named by its HLO name (``%kv_quant.3
  = ...`` gives ``kv_quant.3``; a Pallas kernel's custom call carries the
  kernel's name) and belongs to the program whose execution holds its
  start; programs are named without their hash. Control-flow operations
  (``while``, ``conditional``, ``call``) hold other operations and are left
  out of the per-operation times, not out of busy time.
* Host spans: events named ``bench.*`` on the host plane (the benchmark's
  ``TraceAnnotation`` spans). ``bench.window`` bounds the traced window.
* Busy time is the union of the operation intervals inside the window,
  averaged over the devices; idle share is ``1 - busy / window``.
* Each idle gap is given to the innermost host span that holds its
  midpoint (the one that started last), or ``(no span)``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "(no span)"


@dataclass(frozen=True)
class Ev:
    name: str
    start: float        # ns
    dur: float          # ns
    device: int = -1    # device index, -1 on the host
    module: str = ""    # program the operation belongs to

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: List[Ev] = field(default_factory=list)
    modules: List[Ev] = field(default_factory=list)
    spans: List[Ev] = field(default_factory=list)
    devices: List[int] = field(default_factory=list)


CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%paged_decode_attention.13 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention.13``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def program_name(text: str) -> str:
    """``jit_decode_chunk(9378512885749340201)`` -> ``jit_decode_chunk``."""
    return text.split("(", 1)[0]


def _owner(modules: List[Ev], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i].end:
        return modules[i].name
    return ""


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:"):
            try:
                dev = int(name.rsplit(":", 1)[1])
            except ValueError:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            tr.devices.append(dev)
            mods = sorted((Ev(program_name(e.name), e.start_ns,
                              e.duration_ns, dev)
                           for e in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else [])),
                          key=lambda m: m.start)
            starts = [m.start for m in mods]
            tr.modules.extend(mods)
            for e in lines["XLA Ops"].events:
                tr.ops.append(Ev(op_name(e.name), e.start_ns, e.duration_ns,
                                 dev, _owner(mods, starts, e.start_ns)))
        elif name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append(Ev(e.name, e.start_ns,
                                           e.duration_ns))
    return tr


def window(tr: Trace) -> Tuple[float, float]:
    spans = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    s = max(spans, key=lambda e: e.dur)
    return s.start, s.end


def clip(evs: Sequence[Ev], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for e in evs:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Union of operation intervals in [lo, hi), averaged over devices."""
    if not tr.devices:
        return 0.0
    tot = 0.0
    for d in tr.devices:
        tot += sum(b - a for a, b in union(
            clip([e for e in tr.ops if e.device == d], lo, hi)))
    return tot / len(tr.devices)


def gaps(tr: Trace, lo: float, hi: float,
         device: Optional[int] = None) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi) in which no operation ran on ``device``
    (default: the first device)."""
    if not tr.devices:
        return [(lo, hi)]
    d = tr.devices[0] if device is None else device
    busy = union(clip([e for e in tr.ops if e.device == d], lo, hi))
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Ev], t: float) -> str:
    """Innermost host span (latest start) holding time ``t``."""
    best = None
    for s in spans:
        if s.name == WINDOW_SPAN or not (s.start <= t < s.end):
            continue
        if best is None or s.start > best.start:
            best = s
    return best.name if best is not None else NO_SPAN


def idle_by_span(tr: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Idle ns of the first device, by the host span each gap falls in."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps(tr, lo, hi):
        out[span_at(tr.spans, (a + b) / 2)] += b - a
    return dict(out)


def time_by(evs: Sequence[Ev], lo: float, hi: float, key=lambda e: e.name
            ) -> Dict[str, float]:
    """Device ns per key inside [lo, hi), summed over devices."""
    out: Dict[str, float] = defaultdict(float)
    for e in evs:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out[key(e)] += b - a
    return dict(out)


@dataclass
class Reduced:
    """What the per-layer readers see: seconds, not nanoseconds."""
    window_s: float
    busy_s: float
    op_s: Dict[str, float]          # device seconds per "program/operation"
    module_s: Dict[str, float]      # device seconds per program
    idle_span_s: Dict[str, float]   # idle seconds per host span
    n_devices: int
    # device seconds per program that started inside host spans of each
    # name lying wholly in the window: {"bench.decode_step": {program: s}}
    span_module_s: Dict[str, Dict[str, float]] = field(default_factory=dict)

    def kernel_time(self, program: str, op: str) -> float:
        """Seconds of operations whose name starts with ``op`` inside the
        program named ``program``."""
        tot = 0.0
        for k, v in self.op_s.items():
            mod, _, name = k.rpartition("/")
            if mod == program and name.startswith(op):
                tot += v
        return tot

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_span_s.items(), key=lambda kv: -kv[1])[:n]


def span_modules(tr: Trace, lo: float, hi: float
                 ) -> Dict[str, Dict[str, float]]:
    """Device ns of each program, by the innermost host span (wholly
    inside [lo, hi)) that holds the program's start."""
    inside = [s for s in tr.spans if s.name != WINDOW_SPAN
              and lo <= s.start and s.end <= hi]
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for m in tr.modules:
        best = None
        for s in inside:
            if s.start <= m.start < s.end and (best is None
                                              or s.start > best.start):
                best = s
        if best is not None:
            out[best.name][m.name] += m.dur
    return {k: dict(v) for k, v in out.items()}


def reduce(tr: Trace) -> Reduced:
    lo, hi = window(tr)
    ns = 1e-9
    modules = tr.modules or []
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy_ns(tr, lo, hi) * ns,
        op_s={k: v * ns for k, v in time_by(
            [e for e in tr.ops if e.name.split(".")[0] not in CONTAINERS],
            lo, hi, key=lambda e: f"{e.module}/{e.name}").items()},
        module_s={k: v * ns for k, v in time_by(modules, lo, hi).items()},
        idle_span_s={k: v * ns for k, v in idle_by_span(tr, lo, hi).items()},
        n_devices=len(tr.devices),
        span_module_s={s: {k: v * ns for k, v in d.items()}
                       for s, d in span_modules(tr, lo, hi).items()})
