"""Banked programs traced per bank (layer "sweeps"): the program's
``bank_eval.trace`` spans that ended in the window over its ``explore``
spans that ended there.  The body of a banked program's lane is the
span ``bank_eval.trace`` and runs only while JAX traces the program, so
this is the number of programs traced for each bank: every program of
the bank while ``bank_eval`` builds a new one per call, none once
programs are reused."""
from bench import spans as sp


def read(run):
    spans = sp.in_window(run)
    if spans is None:
        return None
    banks = sum(1 for s in spans if s.name == "explore")
    if not banks:
        return None
    return sum(1 for s in spans if s.name == "bank_eval.trace") / banks
