"""The program's own host spans in the measured window, for the
per-layer readers that read them.

The program records them with ``repro.tracing.span`` on ``time.time()``,
the clock of ``Measured.window_host`` and of the runtime's spans in
``Measured.host["spans"]``.  A program without that recorder, or one
that recorded nothing in the window, gives None, and the reader that
asked finds nothing to read.
"""
from __future__ import annotations

#: spans of the program's own host work that enclose no other such span:
#: sweep set-up, golden baseline, each banked call (its trace, lowering,
#: load and dispatch), the wait for the device's results, row assembly
LEAVES = ("sweep.prep", "explore.baseline", "bank_eval.call",
          "bank_eval.wait", "sweep.rows")


def in_window(run):
    """``repro.tracing.Span``s that ended in the window, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    spans = tracing.spans_between(*run.window_host)
    return spans or None


def clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def covered(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total
