"""Benchmark of the banked resilience sweep on a TPU (see ``run.py``)."""
