"""The readers of the program's own spans (``bench/spans.py``):
``bank_eval.traces_per_bank``, ``sweep.host_prep_share`` and
``device_idle.unattributed``, on hand-made spans, on the recorded TPU
trace of ``data/bank_kernel.xplane.pb``, on a program without the
recorder, and on the spans of a tiny sweep run here."""
import os
import sys
import time

import pytest

from bench import run as bench_run
from bench import trace
from bench.run import Measured, load_reader

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "bank_kernel.xplane.pb")
READERS = ("bank_eval.traces_per_bank", "sweep.host_prep_share",
           "device_idle.unattributed")
T0 = 5000.0


def measured(window_s=10.0, runtime=(), reduced=None):
    return Measured(window_s=window_s, window_host=(T0, T0 + window_s),
                    rows=0, chips=1, peaks={}, macs_per_item=0,
                    eval_items=0, lut_calls=[],
                    host={"spans": list(runtime)}, trace=reduced)


@pytest.fixture
def recorded(monkeypatch):
    """Replace the recorder's spans with hand-made ``(name, start,
    end)`` ones, on the window's clock."""
    from repro import tracing

    spans = []

    def between(t0, t1):
        return [s for s in spans if t0 <= s.end <= t1]

    monkeypatch.setattr(tracing, "spans_between", between)

    def add(name, start, end):
        spans.append(tracing.Span(name, T0 + start, T0 + end,
                                  len(spans) + 1, None, 1, {}))
    return add


def test_traces_per_bank(recorded):
    read = load_reader("bank_eval.traces_per_bank")
    assert read(measured()) is None
    for bank in range(2):
        recorded("explore", bank * 4.0, bank * 4.0 + 4.0)
        for p in range(10):
            recorded("bank_eval.trace", bank * 4.0 + p * 0.1,
                     bank * 4.0 + p * 0.1 + 0.05)
    recorded("bank_eval.trace", -2.0, -1.0)     # ended before the window
    assert read(measured()) == 10.0


def test_host_prep_share(recorded):
    read = load_reader("sweep.host_prep_share")
    assert read(measured()) is None
    recorded("sweep.prep", -0.5, 2.0)            # clipped at the start
    recorded("bank_eval.call", 1.0, 4.0)
    recorded("sweep.rows", 9.5, 10.0)
    recorded("bank_eval.wait", 4.0, 9.0)        # waiting is not work
    recorded("explore", -0.5, 10.0)             # nor is the whole bank
    runtime = [("trace", T0 + 1.5, T0 + 3.0), ("lower", T0 + 2.5, T0 + 3.5)]
    # own work [0, 4] and [9.5, 10]: 4.5 s, less 2 s of the runtime's
    assert read(measured(runtime=runtime)) == pytest.approx(25.0)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(DATA)


def test_unattributed_on_a_recorded_trace(recorded, reduced):
    read = load_reader("device_idle.unattributed")
    run = measured(window_s=reduced.window_s, reduced=reduced)
    assert read(run) is None                    # no program span
    idle = load_reader("device_idle")(run)
    recorded("explore", 0.0, reduced.window_s)  # not a leaf: explains none
    assert read(run) == pytest.approx(idle, rel=1e-9)

    # a runtime span over the first half explains its idle time
    half = reduced.window_s / 2
    w0 = reduced.window[0]
    mid = w0 + half * 1e9
    busy_late = sum(min(e, reduced.window[1]) - max(s, mid)
                    for s, e in reduced.busy[0] if e > mid)
    late_idle = 100.0 * ((reduced.window[1] - mid) - busy_late) \
        / (reduced.window[1] - w0)
    half_run = measured(window_s=reduced.window_s, reduced=reduced,
                        runtime=[("trace", T0 - 1.0, T0 + half)])
    assert read(half_run) == pytest.approx(late_idle, rel=1e-6)

    # a leaf span over the whole window explains every idle moment
    recorded("bank_eval.wait", 0.0, reduced.window_s)
    assert read(run) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_the_recorder(monkeypatch, reduced,
                                                   name):
    """A program that records no spans, as the one before the recorder:
    every reader returns None and raises nothing."""
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    run = measured(window_s=reduced.window_s, reduced=reduced)
    assert load_reader(name)(run) is None


def test_readers_on_a_tiny_sweep():
    """The spans a real sweep records, read as the harness reads them:
    the set-up's warm bank built the Table II program, so the banks
    after it trace none."""
    from bench import cells
    from bench.generators.sweep import Sweep
    from bench.monitor import Monitor

    cell = cells.Cell("resnet8_cifar.table2")
    traffic = {**cell.traffic, "eval_images": 2, "eval_batch": 2,
               "bank_lanes": 2, "variant": "ref"}
    monitor = Monitor().install()
    sweep = Sweep(cell.config, traffic, 3_000_000_021, cells.ROOT)
    sweep.setup()
    t0 = time.time()
    for k in (1, 2):
        sweep.run_bank(k)
    t1 = time.time()
    run = Measured(window_s=t1 - t0, window_host=(t0, t1), rows=4, chips=1,
                   peaks={}, macs_per_item=0, eval_items=2, lut_calls=[],
                   host=monitor.between(t0, t1))
    sweep.close()
    assert bench_run.load_reader("bank_eval.traces_per_bank")(run) == 0
    share = bench_run.load_reader("sweep.host_prep_share")(run)
    assert 0.0 < share < 100.0
