"""Useful model operations per second over the chips' int8 peak (layer
"model step"): every completed row counts as one forward pass of the
network over the eval set, ``2 * MACs`` per eval item (an image, a
sequence) from the configuration's shapes (``bench/counts/<family>.py``),
whatever the program shares or skips.  The int8 peak, because the
emulated datapath multiplies 8-bit integers."""


def read(run):
    if run.rows == 0:
        return None
    ops = 2.0 * run.macs_per_item * run.eval_items * run.rows
    peak = run.chips * run.peaks["int8_ops_per_s"]
    return 100.0 * ops / run.window_s / peak
