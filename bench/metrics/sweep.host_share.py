"""Share of the window in which the host traces, lowers, or compiles and
loads programs from the compilation cache (layer "sweeps"): the union
of the runtime's spans of those steps (``jax.monitoring``), clipped to
the window, over the window.  Nested spans count once."""


def read(run):
    t0, t1 = run.window_host
    spans = sorted((max(s, t0), min(e, t1)) for _, s, e in run.host["spans"]
                   if min(e, t1) > max(s, t0))
    if not spans:
        return None
    busy, end = 0.0, t0
    for s, e in spans:
        if e > end:
            busy += e - max(s, end)
            end = e
    return 100.0 * busy / (t1 - t0)
