"""Banked programs are kept per traced function and static signature
(``repro.approx.layers.bank_program``): a later bank of other
multipliers runs the kept program with its LUTs as arguments, with no
trace; any change of signature builds a new program; and a kept program
keeps neither its function nor its workload alive."""
from __future__ import annotations

import gc
import time
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.approx import layers
from repro.approx.dse import explore
from repro.approx.layers import bank_eval
from repro.approx.specs import BackendSpec, bank_for
from repro.approx.workload import Workload
from repro.core.library import build_default_library

BANK_A = ["mul8u_exact", "mul8u_trunc4", "mul8u_bam_h0_v3"]
BANK_B = ["mul8u_trunc2", "mul8u_trunc6", "mul8u_bam_h1_v2"]
WIDE = ["mul8u_trunc6", "mul12u_c_mul8u_exact_loa4"]
COUNTS = {"a": 96, "b": 48}


@pytest.fixture(scope="module")
def lib():
    lib = build_default_library("tiny")
    lib.add_composed("mul8u_exact", 12, "loa4", samples=512)
    return lib


def toy_traceable():
    """Layer ``a`` then layer ``b`` over fixed inputs."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    w_a = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    w_b = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

    def traceable(policy):
        y = policy.matmul("a", x, w_a)
        return policy.matmul("b", y * (y > 0), w_b)

    return traceable


def fresh(fn):
    """The same computation as a function ``bank_eval`` has not seen, so
    its program is built anew."""
    return lambda policy: fn(policy)


def run(fn, bank, **kw):
    """``bank_eval``'s output, its call's ``reused`` and the number of
    lane traces it recorded."""
    t0 = time.time()
    out = np.asarray(bank_eval(fn, bank, **kw))
    spans = tracing.spans_between(t0, time.time())
    (call,) = [s for s in spans if s.name == "bank_eval.call"]
    traces = sum(s.name == "bank_eval.trace" for s in spans)
    return out, call.attrs["reused"], traces


def test_second_bank_reuses_the_program(lib):
    fn = toy_traceable()
    out_a, reused_a, traces_a = run(fn, bank_for(BANK_A, lib))
    assert (reused_a, traces_a) == (False, 1)
    out_b, reused_b, traces_b = run(fn, bank_for(BANK_B, lib))
    assert (reused_b, traces_b) == (True, 0)
    # the kept program reads the new bank's LUTs: they are arguments
    assert not np.array_equal(out_a, out_b)
    want, reused, _ = run(fresh(fn), bank_for(BANK_B, lib))
    assert not reused
    np.testing.assert_array_equal(out_b, want)


@pytest.mark.parametrize("change", ["layer_pattern", "variant", "base",
                                    "block_m", "wide"])
def test_another_signature_builds_a_new_program(lib, change):
    fn = toy_traceable()
    kw = {"layer_pattern": "b"}
    names, block_m = BANK_A, 512
    run(fn, bank_for(names, lib, block_m=block_m), **kw)
    if change == "layer_pattern":
        kw["layer_pattern"] = "a"
    elif change == "variant":
        kw["variant"] = "pallas"
    elif change == "base":
        kw["base"] = BackendSpec(mode="lut", multiplier="mul8u_trunc2"
                                 ).materialize(lib)
    elif change == "block_m":
        block_m = 256
    else:
        names = WIDE
    bank = bank_for(names, lib, block_m=block_m)
    out, reused, traces = run(fn, bank, **kw)
    assert (reused, traces) == (False, 1)
    want, _, _ = run(fresh(fn), bank, **kw)
    np.testing.assert_array_equal(out, want)


def test_wide_bank_reuses_with_other_lanes(lib):
    fn = toy_traceable()
    run(fn, bank_for(WIDE, lib))
    bank = bank_for(WIDE[::-1], lib)
    out, reused, traces = run(fn, bank)
    assert (reused, traces) == (True, 0)
    want, _, _ = run(fresh(fn), bank)
    np.testing.assert_array_equal(out, want)


def test_another_lane_count_stays_correct(lib):
    fn = toy_traceable()
    run(fn, bank_for(BANK_A[:2], lib))
    bank = bank_for(BANK_B, lib)
    out, reused, traces = run(fn, bank)
    assert reused and traces == 1          # the kept program, new shape
    want, _, _ = run(fresh(fn), bank)
    assert out.shape[0] == 3
    np.testing.assert_array_equal(out, want)


class _Unreferable:
    """A traced function that cannot be held weakly."""
    __slots__ = ("fn",)

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, policy):
        return self.fn(policy)


def test_function_not_held_weakly_builds_each_call(lib):
    fn = _Unreferable(toy_traceable())
    bank = bank_for(BANK_A, lib)
    out_1, reused_1, traces_1 = run(fn, bank)
    out_2, reused_2, traces_2 = run(fn, bank)
    assert (reused_1, traces_1, reused_2, traces_2) == (False, 1, False, 1)
    np.testing.assert_array_equal(out_1, out_2)


def toy_workload() -> Workload:
    traceable = toy_traceable()

    def metrics(policy):
        y = traceable(policy)
        return {"accuracy": jnp.mean(y > 0).astype(jnp.float32)}

    def fn(policy):
        return {k: float(v) for k, v in metrics(policy).items()}

    return Workload(name="toy", fn=fn, metrics=("accuracy",),
                    traceable_metrics=metrics, layer_counts=COUNTS)


def test_explore_banks_trace_once_and_free_with_the_workload(lib):
    """Bank after bank through ``explore``, as a screen of a library
    runs: only the first bank traces its programs, and dropping the
    workload drops them."""
    wl = toy_workload()
    traces = []
    for names in (BANK_A, BANK_B):
        t0 = time.time()
        explore(wl, library=lib, multipliers=names, batch=True)
        spans = tracing.spans_between(t0, time.time())
        calls = [s for s in spans if s.name == "bank_eval.call"]
        assert len(calls) == 1 + len(COUNTS)
        traces.append(sum(s.name == "bank_eval.trace" for s in spans))
    assert traces == [1 + len(COUNTS), 0]
    assert all(c.attrs["reused"] for c in calls)

    fn = weakref.ref(wl.traceable_metrics)
    kept = len(layers._PROGRAMS)
    del wl
    gc.collect()
    assert fn() is None
    assert len(layers._PROGRAMS) == kept - 1
