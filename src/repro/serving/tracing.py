"""Host spans and device named scopes of the serving program.

``span(name, attrs)`` is a context manager around one layer seam. It
enters a ``jax.profiler.TraceAnnotation`` named ``serve.<name>`` carrying
``seq`` (a process-wide span number), ``parent`` (the ``seq`` of the
innermost open span, 0 for none) and the attributes ``attrs()`` returns,
so a profiler trace shows the program's own spans beside the device's
operations. Spans that act on requests carry their ids as ``rids``.

``record(spans, clock)`` attaches a recorder (process-wide, off by
default): every span that closes is then also appended to ``spans`` as a
:class:`Span` timed on ``clock``, the host clock the gateway runs on. The
list stays in memory; whoever attached it writes it out when the run
ends. ``stop()`` detaches it.

With no recorder attached and no profiler running, a span costs one
``TraceAnnotation.is_enabled()`` check: ``attrs`` is not called.

``scoped(name, fn)`` puts what ``fn`` traces under ``jax.named_scope``:
device operations then carry the scope in their metadata, and the jitted
program keeps the name of the function the caller jits.
"""
from __future__ import annotations

import functools
import itertools
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

PREFIX = "serve."


@dataclass
class Span:
    seq: int
    parent: int
    name: str           # ``serve.<name>``
    t0: float           # recorder's clock
    t1: float
    attrs: Dict[str, object] = field(default_factory=dict)


class _Recorder:
    spans: Optional[List[Span]] = None
    clock: Optional[Callable[[], float]] = None
    stack: List[int] = []       # seq of the spans open now, innermost last
    seq = itertools.count(1)


def record(spans: List[Span], clock: Callable[[], float]) -> None:
    """Append every closed span to ``spans``, timed on ``clock``."""
    _Recorder.spans = spans
    _Recorder.clock = clock


def stop() -> None:
    """Detach the recorder."""
    _Recorder.spans = None
    _Recorder.clock = None


def _stat(v):
    # a TraceMe argument that starts with a digit is read back as an
    # integer up to the first non-digit: keep id lists whole as "[1,2]"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(str(x) for x in v) + "]"
    return v


@contextmanager
def span(name: str, attrs: Optional[Callable[[], Dict[str, object]]] = None):
    """``attrs``: the span's attributes, built only when something reads
    them (a recorder or the profiler)."""
    spans = _Recorder.spans
    if spans is None and not TraceAnnotation.is_enabled():
        yield
        return
    attrs = attrs() if attrs is not None else {}
    seq = next(_Recorder.seq)
    parent = _Recorder.stack[-1] if _Recorder.stack else 0
    event = TraceAnnotation(PREFIX + name, seq=seq, parent=parent,
                            **{k: _stat(v) for k, v in attrs.items()})
    clock = _Recorder.clock
    _Recorder.stack.append(seq)
    # the host clock is read right next to the event's own start and end,
    # so the two clocks match span by span
    t0 = clock() if spans is not None else 0.0
    try:
        with event:
            yield
    finally:
        t1 = clock() if spans is not None else 0.0
        _Recorder.stack.pop()
        if spans is not None:
            spans.append(Span(seq, parent, PREFIX + name, t0, t1, attrs))


def scoped(name: str, fn: Callable) -> Callable:
    """``fn`` with everything it traces under ``jax.named_scope(name)``."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return call
