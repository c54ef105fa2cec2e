"""The serving program's own spans and named scopes in a profiler trace,
on one clock with the program's recorder.

The program (``repro.serving.tracing``) writes a ``serve.<seam>`` host
span at each layer seam, with ``seq``, ``parent`` and attributes as the
event's stats, and keeps its device work under ``jax.named_scope``s. With
a recorder attached it also keeps each span on its host clock, under the
same ``seq``. This module reads both sides:

* ``load``: ``trace_reduce.load``'s events, plus the ``serve.*`` host
  events and each device operation's named scope, read from the op
  event's stats (the framework op name the profiler records with it, or
  the ``op_name`` metadata of its HLO text). JAX's own path components
  (``jit(...)``, ``while``, ``body``, ...) and the primitive are dropped:
  ``jit(decode_chunk)/while/body/closed_call/decode/kv_pages/squeeze`` is
  in scope ``decode/kv_pages``.
* ``match_clock``: the offset from the recorder's clock to the trace's is
  the median of the start differences of the spans matched by ``seq``;
  its spread is the largest distance from that median.
* ``decode_kv_pages_share``: the share of the decode chunk program's
  device time under ``decode/kv_pages`` (the per-layer pool copies).

``bench/run.py`` attaches no recorder, keeps no HLO text and reads the
trace through ``trace_reduce`` alone, so nothing here is a benchmark
metric yet; PERF.md (open questions) names the edits that would make it
one. The tests read a recorded chip slice through it.
"""
from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce
from bench.trace_reduce import Ev

PREFIX = "serve."
DECODE_MODULE = "jit_decode_chunk"
KV_PAGES_SCOPE = "decode/kv_pages"
# path components JAX adds around the program's own scopes
_WRAPPERS = {"while", "body", "cond", "closed_call", "core_call", "pjit",
             "scan", "remat", "checkpoint", "shard_map", "custom_jvp_call",
             "custom_vjp_call"}
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = [^\n]*?op_name="([^"]*)"',
                     re.M)


@dataclass(frozen=True)
class ProgramSpan:
    name: str           # ``serve.<seam>``
    start: float        # ns, trace clock
    dur: float
    seq: int
    parent: int
    attrs: Dict[str, object] = field(default_factory=dict, compare=False)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class ProgramTrace:
    trace: trace_reduce.Trace       # as ``trace_reduce.load`` reads it
    scopes: List[str]               # named scope of each of ``trace.ops``
    spans: List[ProgramSpan]        # the program's ``serve.*`` spans


def scope_of(path: str) -> str:
    """The program's named scopes in an operation's framework path (of a
    fusion that merged several, ``a;b``, the first)."""
    parts = path.split(";", 1)[0].split("/")[:-1]
    return "/".join(p for p in parts
                    if p and p not in _WRAPPERS and "(" not in p)


def op_path(stats_) -> str:
    """An op event's framework path: the ``tf_op`` stat, else the
    ``op_name`` metadata in its HLO text (``long_name``), else ""."""
    st = dict(stats_)
    tf_op = st.get("tf_op")
    if isinstance(tf_op, str) and "/" in tf_op:
        return tf_op.split(":", 1)[0]
    m = _OP_NAME.search(str(st.get("long_name", "")))
    return m.group(1) if m else ""


def hlo_scopes(text: str) -> Dict[str, str]:
    """Op name -> named scope of every instruction of a compiled program's
    HLO text (``compiled.as_text()``): where the trace's op events carry
    no framework path, as on a TPU, the program's own HLO gives it, since
    the trace names each operation by its HLO instruction name."""
    out = {}
    for m in _HLO_OP.finditer(text):
        out[m.group(1)] = scope_of(m.group(2))
    return out


def program_span(name: str, start: float, dur: float, stats_) -> ProgramSpan:
    st = dict(stats_)
    return ProgramSpan(name, start, dur, int(st.pop("seq", 0)),
                       int(st.pop("parent", 0)), st)


def load(path: str, hlo: Optional[Dict[str, str]] = None) -> ProgramTrace:
    """``hlo``: program name -> its compiled HLO text, for the scopes of
    operations whose events carry no framework path."""
    from jax.profiler import ProfileData
    tr = trace_reduce.load(path)
    by_name = {k: hlo_scopes(v) for k, v in (hlo or {}).items()}
    scopes, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    scopes.extend(scope_of(op_path(e.stats))
                                  for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(program_span(e.name, e.start_ns, e.duration_ns,
                                          e.stats)
                             for e in ln.events
                             if e.name.startswith(PREFIX))
    if len(scopes) != len(tr.ops):
        raise ValueError(f"{len(scopes)} op scopes for {len(tr.ops)} ops")
    scopes = [sc or by_name.get(e.module, {}).get(e.name, "")
              for e, sc in zip(tr.ops, scopes)]
    return ProgramTrace(tr, scopes, spans)


def match_clock(spans: Sequence[ProgramSpan], recorded
                ) -> Optional[Tuple[float, float, int]]:
    """(offset ns, spread ns, spans matched) from the recorder's clock
    (seconds) to the trace's: ``trace_ns = host_s * 1e9 + offset``. None
    when no ``seq`` matches."""
    t0 = {r.seq: r.t0 for r in recorded}
    diffs = [s.start - t0[s.seq] * 1e9 for s in spans if s.seq in t0]
    if not diffs:
        return None
    mid = statistics.median(diffs)
    return mid, max(abs(d - mid) for d in diffs), len(diffs)


def scope_seconds(pt: ProgramTrace, lo: float, hi: float
                  ) -> Dict[str, float]:
    """Device seconds per ``program/scope`` inside [lo, hi) (ns), control
    flow operations left out as in ``trace_reduce.reduce``."""
    ops = [Ev(e.name, e.start, e.dur, e.device, f"{e.module}/{sc}")
           for e, sc in zip(pt.trace.ops, pt.scopes)
           if e.name.split(".")[0] not in trace_reduce.CONTAINERS]
    return {k: v * 1e-9 for k, v in trace_reduce.time_by(
        ops, lo, hi, key=lambda e: e.module).items()}


def scope_time(scope_s: Dict[str, float], program: str, scope: str) -> float:
    """Seconds of ``program``'s operations under ``scope`` or a scope
    nested in it."""
    return sum(v for k, v in scope_s.items()
               if k.partition("/")[0] == program
               and (k.partition("/")[2] + "/").startswith(scope + "/"))


def idle_by_program_span(pt: ProgramTrace, lo: float, hi: float
                         ) -> Dict[str, float]:
    """Idle seconds of the first device in [lo, hi) (ns), by the innermost
    ``serve.*`` span that holds each gap's midpoint."""
    t = trace_reduce.Trace(ops=pt.trace.ops, devices=pt.trace.devices,
                           spans=[Ev(s.name, s.start, s.dur)
                                  for s in pt.spans])
    return {k: v * 1e-9
            for k, v in trace_reduce.idle_by_span(t, lo, hi).items()}


def decode_kv_pages_share(pt: ProgramTrace) -> Optional[float]:
    """Percent of ``jit_decode_chunk``'s device time in the traced window
    spent in operations under ``decode/kv_pages``; None when the window
    holds no such time."""
    lo, hi = trace_reduce.window(pt.trace)
    kv = scope_time(scope_seconds(pt, lo, hi), DECODE_MODULE, KV_PAGES_SCOPE)
    total = trace_reduce.time_by(pt.trace.modules, lo, hi).get(
        DECODE_MODULE, 0.0) * 1e-9
    if kv <= 0 or total <= 0:
        return None
    return 100.0 * kv / total
