"""Share of the traced window that no span explains (layer "device"):
the device runs no operation (``bench.trace``), and neither a leaf span
of the program's host work (``bench.spans.LEAVES``) nor a runtime trace,
lowering or compile-or-load span covers the host.  Host spans move to
the trace's clock by the offset ``bench/run.py`` uses for the idle
gaps: the window's start on the host clock less its start in the
trace.  Averaged over the devices."""
from bench import spans as sp


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    spans = sp.in_window(run)
    if spans is None:
        return None
    w0, w1 = run.trace.window
    offset = run.window_host[0] - w0 / 1e9
    host = [(s.start, s.end) for s in spans if s.name in sp.LEAVES]
    host += [(s, e) for _, s, e in run.host["spans"]]
    host = sp.clip([((s - offset) * 1e9, (e - offset) * 1e9)
                    for s, e in host], w0, w1)
    unexplained = [(w1 - w0) - sp.covered(list(busy) + host)
                   for busy in run.trace.busy]
    return 100.0 * sum(unexplained) / len(unexplained) / (w1 - w0)
