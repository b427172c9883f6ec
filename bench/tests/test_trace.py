"""The trace reduction, on a small profiler trace recorded on a TPU v5e:
a two-lane banked LUT kernel run three times inside a ``bench.window``
annotation (``data/bank_kernel.xplane.pb``)."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "bank_kernel.xplane.pb")
KERNEL = ("approx_matmul_lut_bank_pallas",
          'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(DATA)


def test_window_busy_and_kernel(reduced):
    assert reduced.window_s == pytest.approx(0.038797489)
    assert len(reduced.busy) == 1
    assert 0 < reduced.kernel_s(*KERNEL) <= reduced.busy_s < reduced.window_s
    # three launches of the kernel, nearly all of the device's work
    assert sum(1 for name, *_ in reduced.ops
               if all(p in name for p in KERNEL)) == 3
    assert reduced.kernel_s(*KERNEL) > 0.9 * reduced.busy_s


def test_self_times_add_up_to_busy(reduced):
    assert sum(reduced.op_s().values()) == pytest.approx(reduced.busy_s,
                                                         rel=1e-6)
    top = max(reduced.op_s().items(), key=lambda kv: kv[1])[0]
    assert "approx_matmul_lut_bank_pallas" in top


def test_idle_gaps_fill_the_window(reduced):
    gaps = reduced.idle_gaps([], 0.0)
    assert sum(g for _, g in gaps) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-6)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps),
                                          reverse=True)
    # a host span covering the window names every gap
    t0 = reduced.window[0] / 1e9
    labelled = reduced.idle_gaps([("trace", t0, t0 + 1.0)], 0.0)
    assert all(name.startswith("trace:") and "other" not in name
               for name, _ in labelled)
    assert all(name.startswith("other:") for name, _ in gaps)


def test_nesting():
    out = trace.nest([(0, 10, "while"), (1, 3, "a"), (4, 9, "b"),
                      (5, 6, "c"), (12, 13, "d")])
    got = {name: (own, leaf) for name, _, _, own, leaf in out}
    assert got == {"while": (3, False), "a": (2, True), "b": (4, False),
                   "c": (1, True), "d": (1, True)}


def test_union():
    assert trace._union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]
    assert trace.short_name("%fusion.12 = f32[2] fusion(f32[2] %x)") == \
        "fusion"
