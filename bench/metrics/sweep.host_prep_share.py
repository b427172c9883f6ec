"""Share of the window in which the host does the sweep's own work
(layer "sweeps"): the union of the program's spans ``sweep.prep``,
``explore.baseline``, ``bank_eval.call`` and ``sweep.rows``, clipped to
the window, less the runtime's trace, lowering and compile-or-load
spans (``Measured.host``), over the window.  It is the host time the
runtime's spans do not name: packing banks, power and cost maps, row
assembly, dispatch."""
from bench import spans as sp

OWN = ("sweep.prep", "explore.baseline", "bank_eval.call", "sweep.rows")


def read(run):
    spans = sp.in_window(run)
    if spans is None:
        return None
    t0, t1 = run.window_host
    own = sp.clip([(s.start, s.end) for s in spans if s.name in OWN], t0, t1)
    if not own:
        return None
    runtime = sp.clip([(s, e) for _, s, e in run.host["spans"]], t0, t1)
    only_own = sp.covered(own + runtime) - sp.covered(runtime)
    return 100.0 * only_own / (t1 - t0)
