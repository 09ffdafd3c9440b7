"""Layer spans: one name for a layer on the device trace and on the host.

``with layer("conv3"):`` does three things at once:

* enters ``jax.named_scope("conv3")``, so every operation traced inside
  carries ``.../conv3/...`` in its HLO ``op_name`` metadata (metadata
  only: the compiled program is the same);
* records a host span in memory: name, start and end on
  ``time.perf_counter_ns``, the enclosing span's index, and attributes;
* enters ``jax.profiler.TraceAnnotation("conv3")``, so a profiler run
  with the host tracer on puts the span on the trace's own clock.

Spans are recorded where Python runs: when a forward is traced (before it
is compiled) and in set-up (calibration, quantization, planning).  A
compiled forward runs no Python, so nothing here runs per request.  The
store keeps the last :data:`MAX_SPANS` closed spans, in the order they
closed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Optional

import jax

__all__ = ["MAX_SPANS", "Span", "layer", "self_ns", "spans"]

#: Closed spans kept; the oldest are dropped first.
MAX_SPANS = 4096


@dataclasses.dataclass
class Span:
    index: int  # unique, in the order spans opened
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]  # index of the enclosing span (same thread)
    attrs: dict

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


_closed: collections.deque = collections.deque(maxlen=MAX_SPANS)
_index = itertools.count()
_open = threading.local()


@contextlib.contextmanager
def layer(name: str, **attrs):
    """Name the work inside for the trace and record its host span.
    Yields the span's ``attrs``, so a count known only at the end (a
    counter's delta) can be added before the span closes."""
    stack = _open.__dict__.setdefault("stack", [])
    sp = Span(next(_index), name, time.perf_counter_ns(), 0,
              stack[-1].index if stack else None, dict(attrs))
    stack.append(sp)
    try:
        with jax.named_scope(name), jax.profiler.TraceAnnotation(name, **attrs):
            yield sp.attrs
    finally:
        sp.end_ns = time.perf_counter_ns()
        stack.pop()
        _closed.append(sp)


def spans() -> list:
    """The closed spans kept, in the order they opened."""
    return sorted(_closed, key=lambda s: s.index)


def self_ns(span: Span, among: Optional[list] = None) -> int:
    """``span``'s duration less the time its child spans cover (children
    of one thread run one after another, so they do not overlap)."""
    among = spans() if among is None else among
    return span.dur_ns - sum(s.dur_ns for s in among if s.parent == span.index)
