"""Compile, trace and cache-load events of the JAX runtime, with their
host-clock spans.

A copy of the listener of ``repro.launch.compile_cache.trace_audit``,
extended to keep each event's start and end: JAX publishes them through
``jax.monitoring`` for every program it traces, lowers and compiles or
loads from the persistent compilation cache.  ``fresh_compiles`` counts
backend compiles that no cache hit served.  Listeners are global and
cannot be removed, so one ``Monitor`` is installed per process.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

#: host activity of each span event, as the breakdown labels it
LABELS = {TRACE: "trace", LOWER: "lower", COMPILE: "compile_or_cache_load"}


@dataclass
class Monitor:
    """Everything the runtime published since ``install``: spans
    ``(label, start_s, end_s)`` on ``time.time()``, cache hits with
    their time, and cache-load durations."""

    spans: list = field(default_factory=list)
    hits: list = field(default_factory=list)
    loads: list = field(default_factory=list)

    def install(self) -> "Monitor":
        def on_span(event, start, end, **kw):
            if event in LABELS:
                self.spans.append((LABELS[event], start, end))

        def on_event(event, **kw):
            if event == CACHE_HIT:
                self.hits.append(time.time())

        def on_duration(event, secs, **kw):
            if event == CACHE_LOAD:
                self.loads.append((time.time(), secs))

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def between(self, t0: float, t1: float) -> dict:
        """Counts and summed seconds of the events that ended in
        ``[t0, t1]`` (``time.time()``)."""
        spans = [s for s in self.spans if t0 <= s[2] <= t1]
        compiles = sum(1 for s in spans if s[0] == LABELS[COMPILE])
        hits = sum(1 for t in self.hits if t0 <= t <= t1)
        secs = {label: 0.0 for label in LABELS.values()}
        for label, start, end in spans:
            secs[label] += end - start
        return {"spans": spans, "programs": compiles, "cache_hits": hits,
                "fresh_compiles": compiles - hits, "seconds": secs,
                "cache_load_s": sum(s for t, s in self.loads
                                    if t0 <= t <= t1)}
