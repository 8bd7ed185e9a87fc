"""Named spans of the program's host calls, and the names of its device scopes.

``span(name)`` marks one host call (``plan``, ``run``, ``run_batch``) in two
places at once:

* a ``jax.profiler.TraceAnnotation`` of that name, which lands in the
  profiler's host plane on the same clock as the device trace, so a gap in
  the device's work can be attributed to the host call open during it;
* a bounded in-memory record of ``Span(name, parent, start_ns, end_ns)``
  (``time.perf_counter_ns``), readable with ``recorded()`` whether or not a
  profiler runs, and emptied with ``clear()``.

Spans open at call granularity only: the super-step loop runs on the
device, where ``jax.named_scope`` labels its phases in the compiled
program's ``op_name`` metadata instead (``kernels/ops.py``):

* ``stencil.pad``           edge-padding the grid and the aux field into the
                            blocked layout, in the jitted ``run_pallas*``
                            entry points (the Pallas backend pads op by op
                            before its executable, where no scope reaches);
* ``stencil.superstep``     the fused streaming kernel of one super-step;
* ``stencil.halo_refresh``  rewriting the padded carry's padding strips
                            in place between super-steps;
* ``stencil.unpad``         slicing the result out of the padded carry.

A scope is metadata only: it changes no fusion, layout or code of the
executable.
"""
from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager
from typing import NamedTuple, Optional

import jax

#: spans kept; the oldest are dropped beyond this
MAX_SPANS = 4096


class Span(NamedTuple):
    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int


_RECORD: collections.deque = collections.deque(maxlen=MAX_SPANS)
_OPEN = threading.local()


@contextmanager
def span(name: str):
    """Record the enclosed host call as ``name``; usable as a decorator.
    Its parent is the span this thread has open around it, if any."""
    stack = _OPEN.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _RECORD.append(Span(name, parent, start, time.perf_counter_ns()))
        stack.pop()


def recorded() -> list:
    """The recorded spans, oldest first by end (a parent follows its
    children)."""
    return list(_RECORD)


def clear() -> None:
    _RECORD.clear()
