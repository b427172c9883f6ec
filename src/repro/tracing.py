"""Host spans of the program, and its one ``jax.monitoring`` listener.

``span(name, **attrs)`` times a block of the program's host work:

    with span("sweep.prep") as attrs:
        ...
        attrs["built"] = True      # recorded with the span

Each span is kept as a ``Span`` ``(name, start, end, id, parent, root,
attrs)``.  ``start`` and ``end`` are ``time.time()``, the clock of the
runtime's ``jax.monitoring`` spans; ``parent`` is the span open around
it on the same thread, and ``root`` the outermost one (its own id for a
span opened with none around it), so every span of one
``repro.approx.dse.explore`` call shares the id of that call's
``explore`` span.  Each span is also a ``jax.profiler.TraceAnnotation``
of the same name, with the attributes given on entry, so a profiler
trace shows it on the host's timeline beside the device's operations.

The module registers the program's one ``jax.monitoring`` listener when
it is imported.  It records the runtime's trace, lowering and
compile-or-cache-load spans as ``Span``s too (``jax.trace``,
``jax.lower``, ``jax.compile``, with the runtime's ``fun_name``), whose
parent is the program span open when the step ended, and counts
backend compiles and persistent-cache hits for
``repro.launch.compile_cache.trace_audit``.

Spans are kept in memory, the newest ``MAX_SPANS``: ``spans_between``
returns those that end in an interval.  Nothing is written to a file;
a profiler trace is the operator's view of the same spans.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax

#: events of ``jax/_src/dispatch.py`` and ``jax/_src/compilation_cache.py``
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the runtime's span events, by the name their spans are recorded under
RUNTIME_SPANS = {TRACE_EVENT: "jax.trace", LOWER_EVENT: "jax.lower",
                 COMPILE_EVENT: "jax.compile"}

#: spans kept.  A bank of the banked sweep records 900-8,400 (TPU v5e),
#: nearly all of them the runtime's traces of the jitted functions its
#: programs call
MAX_SPANS = 1 << 16


class Span(NamedTuple):
    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    root: int
    attrs: dict


@dataclass(frozen=True)
class RuntimeCounts:
    """Backend compiles (each fresh compile or persistent-cache load of
    a program), their summed seconds, and persistent-cache hits."""

    compiles: int = 0
    compile_secs: float = 0.0
    cache_hits: int = 0


class Recorder:
    """Spans and runtime counts of one process (see the module's
    docstring); ``span`` and the runtime listener write here."""

    def __init__(self, maxlen: int = MAX_SPANS):
        self._spans: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._open = threading.local()
        self._lock = threading.Lock()
        self._counts = RuntimeCounts()

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _new(self, stack: list) -> tuple[int, Optional[int], int]:
        """A new span's id, parent and root, under the open ``stack``."""
        sid = next(self._ids)
        if not stack:
            return sid, None, sid
        return sid, stack[-1], stack[0]

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record the block as the span ``name``; yields ``attrs``, to
        which the block may add."""
        stack = self._stack()
        sid, parent, root = self._new(stack)
        stack.append(sid)
        start = time.time()
        try:
            with jax.profiler.TraceAnnotation(name, **attrs):
                yield attrs
        finally:
            end = time.time()
            stack.pop()
            self._spans.append(Span(name, start, end, sid, parent, root,
                                    attrs))

    def on_runtime_span(self, event: str, start: float, end: float,
                        **kw) -> None:
        name = RUNTIME_SPANS.get(event)
        if name is None:
            return
        sid, parent, root = self._new(self._stack())
        self._spans.append(Span(name, start, end, sid, parent, root, kw))
        if event == COMPILE_EVENT:
            with self._lock:
                c = self._counts
                self._counts = RuntimeCounts(c.compiles + 1,
                                             c.compile_secs + (end - start),
                                             c.cache_hits)

    def on_runtime_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                c = self._counts
                self._counts = RuntimeCounts(c.compiles, c.compile_secs,
                                             c.cache_hits + 1)

    def spans_between(self, t0: float, t1: float) -> list[Span]:
        """Spans that ended in ``[t0, t1]`` (``time.time()``), in the
        order they ended."""
        return [s for s in list(self._spans) if t0 <= s.end <= t1]

    def runtime_counts(self) -> RuntimeCounts:
        return self._counts


_RECORDER = Recorder()
jax.monitoring.register_event_time_span_listener(_RECORDER.on_runtime_span)
jax.monitoring.register_event_listener(_RECORDER.on_runtime_event)

span = _RECORDER.span
spans_between = _RECORDER.spans_between
runtime_counts = _RECORDER.runtime_counts
