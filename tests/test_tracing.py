"""The span recorder (``repro.tracing``): the span tree of a banked
``explore``, the runtime's spans beside the program's, the profiler's
view of a span, the bound on what is kept, and the names the compiled
banked program carries."""
from __future__ import annotations

import glob
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.approx.dse import explore
from repro.approx.layers import bank_program
from repro.approx.resilience import BankableEval
from repro.approx.specs import bank_for
from repro.core.library import build_default_library
from repro.launch.compile_cache import trace_audit

MULTS = ["mul8u_exact", "mul8u_trunc4"]
COUNTS = {"a": 10, "b": 20}
LEAVES = {"explore.baseline", "sweep.prep", "bank_eval.call",
          "bank_eval.wait", "sweep.rows"}


@pytest.fixture(scope="module")
def lib():
    return build_default_library("tiny")


def two_layer_eval():
    """A bankable two-layer toy model: layer ``a`` then layer ``b``."""
    x = jnp.asarray(np.linspace(-2, 2, 96).reshape(12, 8), jnp.float32)
    w = jnp.asarray(np.linspace(-1, 1, 32).reshape(8, 4), jnp.float32)

    def traceable(policy):
        return jnp.mean(policy.matmul("b", policy.matmul("a", x, w), w.T))

    return BankableEval(fn=lambda p: float(traceable(p)),
                        traceable=traceable)


@pytest.fixture(scope="module")
def explored(lib):
    """Spans of two banked ``explore`` calls, each with its own
    ``explore`` root."""
    ev = two_layer_eval()
    calls = []
    for _ in range(2):
        t0 = time.time()
        explore(ev, COUNTS, lib, multipliers=MULTS, batch=True,
                quality_bound=0.01)
        calls.append(tracing.spans_between(t0, time.time()))
    return calls


def test_one_explore_root_per_call(explored):
    for spans in explored:
        roots = [s for s in spans if s.name == "explore"]
        assert len(roots) == 1
        root = roots[0]
        assert root.parent is None and root.root == root.id
        assert root.attrs == {"lanes": len(MULTS), "per_layer": True}
        assert all(s.root == root.id for s in spans)
        assert all(root.start <= s.start <= s.end <= root.end
                   for s in spans)
    assert explored[0][-1].id != explored[1][-1].id


def test_span_tree_of_a_banked_explore(explored):
    for k, spans in enumerate(explored):
        by_id = {s.id: s for s in spans}
        root = next(s for s in spans if s.name == "explore")
        names = [s.name for s in spans if not s.name.startswith("jax.")]
        for name in LEAVES:
            assert all(s.parent == root.id
                       for s in spans if s.name == name), name
        assert names.count("explore.baseline") == 1
        assert names.count("sweep.prep") == 2       # all-layers, per-layer
        calls = [s for s in spans if s.name == "bank_eval.call"]
        assert [c.attrs["program"] for c in calls] == ["all", "a", "b"]
        assert names.count("bank_eval.wait") == len(calls)
        # one row span per sweep's bank, two in explore, one selection
        assert names.count("sweep.rows") == len(calls) + 3
        for s in spans:
            if s.name == "bank_eval.trace":
                parent = by_id[s.parent]
                assert parent.name == "bank_eval.call"
                assert parent.attrs["program"] == s.attrs["program"]
                assert parent.start <= s.start <= s.end <= parent.end
        # the bank is packed by the first sweep of the first call only
        built = [s.attrs["built"] for s in spans if s.name == "sweep.prep"]
        assert built == ([True, False] if k == 0 else [False, False])


def test_traces_of_banked_programs_are_counted_where_they_happen(explored):
    """Each trace of a banked program runs its lane body once: as many
    ``bank_eval.trace`` spans as the runtime's trace events of the
    banked programs, which nest in the call that traced them.  No fixed
    count: a program traced once and reused gives none."""
    for spans in explored:
        by_id = {s.id: s for s in spans}
        bodies = [s for s in spans if s.name == "bank_eval.trace"]
        runtime = [s for s in spans if s.name == "jax.trace"
                   and s.attrs.get("fun_name", "").startswith("bank_")]
        assert len(bodies) == len(runtime)
        for s in spans:
            if s.name.startswith("jax.") and "bank_" in s.attrs.get(
                    "fun_name", ""):
                assert by_id[s.parent].name == "bank_eval.call"


def test_span_in_profiler_host_plane(tmp_path):
    """A span is a profiler annotation: it shows on the host's
    timeline, inside the annotation that enclosed it."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.enclosing"):
            with tracing.span("test.inner", lanes=3):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("test."):
                        events[ev.name.split("#")[0]] = (
                            ev.start_ns, ev.start_ns + ev.duration_ns)
    outer, inner = events["test.enclosing"], events["test.inner"]
    assert outer[0] <= inner[0] < inner[1] <= outer[1]


def test_recorder_keeps_the_newest_spans():
    rec = tracing.Recorder(maxlen=8)
    for i in range(20):
        with rec.span("s", i=i):
            pass
    kept = rec.spans_between(0.0, time.time())
    assert [s.attrs["i"] for s in kept] == list(range(12, 20))
    assert tracing.Recorder()._spans.maxlen == tracing.MAX_SPANS


def test_span_nesting_is_per_thread_and_survives_errors():
    rec = tracing.Recorder()
    seen = {}

    def other():
        with rec.span("other") as attrs:
            attrs["tid"] = threading.get_ident()

    with rec.span("outer"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with pytest.raises(ValueError):
            with rec.span("failing"):
                raise ValueError("inside a span")
        with rec.span("after"):
            pass
    for s in rec.spans_between(0.0, time.time()):
        seen[s.name] = s
    outer = seen["outer"]
    assert seen["other"].parent is None
    assert seen["other"].root == seen["other"].id
    assert "tid" in seen["other"].attrs
    assert seen["failing"].parent == outer.id
    assert seen["after"].parent == outer.id


def test_runtime_counts_and_ids_under_threads():
    """Compiles and cache hits counted from many threads at once lose
    no update, and every record gets its own id."""
    rec = tracing.Recorder()
    n_threads, per_thread = 16, 500

    def work():
        for _ in range(per_thread):
            rec.on_runtime_span(tracing.COMPILE_EVENT, 0.0, 0.5,
                                fun_name="f")
            rec.on_runtime_event(tracing.CACHE_HIT_EVENT)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    counts = rec.runtime_counts()
    total = n_threads * per_thread
    assert (counts.compiles, counts.cache_hits) == (total, total)
    assert counts.compile_secs == pytest.approx(0.5 * total)
    spans = rec.spans_between(0.0, 1.0)
    assert len({s.id for s in spans}) == len(spans) == total


def test_trace_audit_reads_the_recorder():
    t0 = time.time()
    before = tracing.runtime_counts()
    with trace_audit() as counts:
        jax.jit(lambda x: x * 4.5 + 0.25)(jnp.ones((11,))).block_until_ready()
    after = tracing.runtime_counts()
    assert counts.compiles >= 1
    assert counts.compiles <= after.compiles - before.compiles
    compiles = [s for s in tracing.spans_between(t0, time.time())
                if s.name == "jax.compile"]
    assert len(compiles) >= counts.compiles


def _scopes(text: str) -> set[str]:
    """Names in the name stacks of a lowered module's locations."""
    stacks = [p for p in re.findall(r'loc\("([^"]*)"', text) if "/" in p]
    return {w for p in stacks for w in re.findall(r"[\w.]+", p)}


@pytest.mark.parametrize("layer,module", [(None, "jit_bank_all"),
                                          ("b", "jit_bank_b")])
def test_banked_program_names(lib, layer, module):
    """The banked program is a named module, and its table build,
    quantization and dequantization carry name scopes."""
    ev = two_layer_eval()
    jitted, args = bank_program(ev.traceable, bank_for(MULTS, lib),
                                variant="pallas", layer_pattern=layer)
    text = jitted.lower(*args).as_text(debug_info=True)
    assert f"module @{module} " in text
    assert {"lut_tables", "quantize", "dequant"} <= _scopes(text)
    assert "approx_matmul_lut_bank_pallas" in text
