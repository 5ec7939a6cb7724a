"""Spans and lowering counts: the program's one tracing system.

A span marks one stretch of host work, such as one decode step of the
serving engine.  It is recorded only while a ``jax.profiler`` trace is
being taken (``jax.profiler.trace`` or ``start_trace``), and then twice:

* as the annotation ``repro:<name>`` in the profiler's own trace, on the
  same clock as the device's events;
* as a :class:`Span` in an in-memory record, which :func:`recorded`
  returns, with the ids the caller gave it.

With the profiler off, :func:`span` costs one check and returns a shared
context that does nothing.

:func:`lowerings` counts the programs JAX lowered, per jitted function.
It is always on: it listens for JAX's lowering event, which fires once
per new program and never in a warm step.  :func:`attention_paths`,
also always on, counts the self-attention layers traced, by the path each
lowered and the share of its S × S scores that path computes.

  with spans.span("serve.slot", slot=0, requests=(3, 4)):
      ...
"""
from __future__ import annotations

import collections
import contextlib
import re
import threading
import time
from typing import Dict, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

LIMIT = 1 << 18
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Span:
    """One span: its name, start and end on ``time.perf_counter_ns``
    (``end`` is None while it is open), the span it ran inside, its ids,
    and the spans that ran inside it."""

    __slots__ = ("name", "start", "end", "parent", "ids", "children")

    def __init__(self, name: str, start: int, end: Optional[int] = None,
                 parent: Optional["Span"] = None, ids: Optional[dict] = None):
        self.name, self.start, self.end = name, start, end
        self.parent, self.ids = parent, ids if ids is not None else {}
        self.children: List[Span] = []
        if parent is not None:
            parent.children.append(self)

    @property
    def ns(self) -> int:
        """The span's duration in nanoseconds."""
        return self.end - self.start


def self_ns(span: Span) -> int:
    """The span's duration less the part of it that its children cover."""
    covered, reach = 0, span.start
    for c in sorted(span.children, key=lambda c: c.start):
        a, b = max(c.start, reach), min(c.end, span.end)
        if b > a:
            covered += b - a
            reach = b
    return span.ns - covered


class _Record:
    """The spans of the latest stretch of profiling, and the open ones."""

    def __init__(self):
        self.spans: List[Span] = []
        # set whenever a span finds the profiler off: the next span that
        # finds it on starts a new stretch
        self.stale = False
        self.local = threading.local()

    def stack(self) -> List[Span]:
        """This thread's open spans, innermost last."""
        try:
            return self.local.stack
        except AttributeError:
            self.local.stack = []
            return self.local.stack


_RECORD = _Record()
_OFF = contextlib.nullcontext()


class _On:
    """A span while the profiler records."""

    __slots__ = ("name", "ids", "annotation")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids = name, ids

    def __enter__(self) -> Span:
        rec = _RECORD
        if rec.stale:
            rec.spans, rec.stale = [], False
        stack = rec.stack()
        parent = stack[-1] if stack else None
        ids = self.ids
        if parent is not None and parent.ids:
            ids = {**parent.ids, **ids} if ids else parent.ids
        self.annotation = TraceAnnotation(f"repro:{self.name}")
        self.annotation.__enter__()
        s = Span(self.name, time.perf_counter_ns(), None, parent, ids)
        if len(rec.spans) < LIMIT:
            rec.spans.append(s)
        stack.append(s)
        return s

    def __exit__(self, *exc):
        s = _RECORD.stack().pop()
        s.end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        return False


def span(name: str, **ids):
    """A context manager that records the work inside it as ``name``.

    ``ids`` identify what the work was for (a slot, its requests, a
    step's rows).  A span carries its parent's ids with its own added, so
    every span under one slot carries that slot's ids.  With the profiler
    off it records nothing.
    """
    if not TraceAnnotation.is_enabled():
        _RECORD.stale = True
        return _OFF
    return _On(name, ids)


def recorded() -> List[Span]:
    """The finished spans of the latest stretch of profiling, in the
    order they started.  A stretch starts with the first span recorded
    after one that found the profiler off.  It keeps its first
    ``LIMIT`` spans; later ones reach the profiler's trace only."""
    return [s for s in _RECORD.spans if s.end is not None]


def clear() -> None:
    """Empty the record."""
    _RECORD.spans = []


def totals(spans: List[Span]) -> Dict[str, tuple]:
    """Per span name: ``(count, total ns, self ns)``."""
    out: Dict[str, list] = {}
    for s in spans:
        t = out.setdefault(s.name, [0, 0, 0])
        t[0] += 1
        t[1] += s.ns
        t[2] += self_ns(s)
    return {k: tuple(v) for k, v in out.items()}


_LOWERED: collections.Counter = collections.Counter()
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _count_lowering(event: str, duration: float, fun_name: str = "?", **_):
    if event == LOWERING:
        m = _WRAPPED.match(fun_name)
        _LOWERED[m.group(1) if m else fun_name] += 1


jax.monitoring.register_event_duration_secs_listener(_count_lowering)


def lowerings() -> Dict[str, int]:
    """Programs lowered so far in this process, per jitted function name
    (``prefill_step``, ``serve_step``, ``_argmax``, ...).  Each lowering
    ends in a compile or a read of the persistent compile cache."""
    return dict(_LOWERED)


_ATTENTION: collections.Counter = collections.Counter()


def note_attention(path: str, share: float) -> None:
    """Count one traced self-attention layer that lowered ``path`` and
    computes ``share`` of its S × S scores.  Called at trace time only,
    so a warm step counts nothing."""
    _ATTENTION[(path, share)] += 1


def attention_paths() -> Dict[tuple, int]:
    """Self-attention layers traced so far in this process, per
    ``(path, share of the S × S scores computed)``: ``("dense", 1.0)``
    or ``("causal_blocks", 0.625)`` for four query blocks."""
    return dict(_ATTENTION)
