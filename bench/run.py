#!/usr/bin/env python3
"""Benchmark of the banked resilience sweep on a TPU: one cell per run.

    python3 bench/run.py --workload resnet8_cifar.table2 --seed 7 \\
        --seconds 45 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
the mix names its generator (``bench/generators/<name>.py``).  A run:

1. stops with a non-zero exit and no result unless JAX finds as many
   TPU chips as the cell asks for;
2. sets up: the persistent compilation cache at a fixed path in the
   checkout (``JAX_COMPILATION_CACHE_DIR`` wins), then the generator's
   set-up, which warms every program the window runs.  ``setup_s`` is
   the time from process start to the window's start;
3. measures for ``--seconds``: banks run one after another while the
   time so far plus the longest bank so far fits; one bank always runs.
   ``rows_per_s`` is every row of the completed banks over the time from
   the window's start to the last bank's completion.  A fresh XLA
   compile inside the window (``bench/monitor.py``) fails the run;
4. with ``--trace 1`` the window is traced by the JAX profiler and the
   per-layer metrics (``bench/metrics/<name>.py``) are read from the
   trace, the runtime's compile spans and the counts of ``count.py``;
5. reads the device's peak memory, frees the program's state, and
   compares a sample of the window's rows with the plain reference
   (``bench/correct.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its
limit.  The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache_bench")


@dataclass
class Measured:
    """What the window did; the per-layer readers take it (see
    ``bench/metrics/__init__.py``)."""

    window_s: float
    window_host: tuple
    rows: int
    chips: int
    peaks: dict
    macs_per_image: int
    eval_images: int
    lut_calls: list
    host: dict
    trace: object = None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def lut_calls(cfg: dict, traffic: dict, programs: list, banks: int) -> list:
    """``(operations, bytes)`` of every LUT matmul launch of ``banks``
    completed banks: each program runs each of its LUT matmuls once per
    eval batch, for all the bank's lanes in one launch."""
    from bench.count import lut_matmul_work, program_lut_matmuls

    per_bank = []
    for layer in programs:
        for _, m, k, n, shared in program_lut_matmuls(
                cfg, traffic["eval_batch"], layer):
            per_bank.append(lut_matmul_work(m, k, n, shared,
                                            traffic["bank_lanes"]))
    batches = traffic["eval_images"] // traffic["eval_batch"]
    return per_bank * (batches * banks)


def breakdown(trace, host_spans, offset_s) -> dict:
    ops = sorted(trace.op_s().items(), key=lambda kv: -kv[1])[:10]
    gaps = trace.idle_gaps(host_spans, offset_s)[:10]
    return {"device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[label, s] for label, s in gaps]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cells, device
    cell = cells.Cell(args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoDevice as e:
        log(f"[bench] {e}")
        return 2
    out = run_cell(cell, devices, args.seed, args.seconds, bool(args.trace))
    if out is None:
        return 3
    print(json.dumps(out), flush=True)
    return 0


def run_cell(cell, devices, seed: int, seconds: float, trace: bool):
    """One run of ``cell`` on ``devices``: the result object, or None
    where a program compiled inside the window."""
    import jax

    from bench import correct, device
    from bench.count import macs_per_image
    from bench.monitor import Monitor
    from repro.launch.compile_cache import enable_compile_cache

    peaks = device.peaks(devices[0].device_kind)
    enable_compile_cache(CACHE_DIR)
    monitor = Monitor().install()

    cfg, traffic = cell.config, cell.traffic
    gen = importlib.import_module(f"bench.generators.{traffic['generator']}")
    sweep = gen.Sweep(cfg, traffic, seed, ROOT)
    sweep.setup()
    programs = sweep.programs_per_bank()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace_dir:
        # host spans of the harness and the runtime only: the Python
        # tracer would record every call and slow the host it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    rows, bank_s = [], []
    anchor = time.time()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.monotonic()
        setup_s = t0 - _PROCESS_START
        k = 0
        while True:
            tb = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.bank"):
                rows += sweep.run_bank(k)
            t1 = time.monotonic()
            bank_s.append(t1 - tb)
            k += 1
            if t1 - t0 + max(bank_s) > seconds:
                break
    window_s = t1 - t0
    window_host = (anchor, anchor + window_s)
    if trace_dir:
        jax.profiler.stop_trace()
    host = monitor.between(*window_host)
    record = device.record(devices)
    log(f"[bench] {k} banks, {len(rows)} rows in {window_s:.3f} s "
        f"(banks {[round(b, 3) for b in bank_s]}); set-up {setup_s:.3f} s; "
        f"window: {host['programs']} programs, {host['cache_hits']} "
        f"cache hits, {host['fresh_compiles']} fresh compiles")
    if host["fresh_compiles"]:
        log(f"[bench] {host['fresh_compiles']} programs compiled inside "
            f"the window: the set-up did not warm every program")
        return None

    run = Measured(window_s=window_s, window_host=window_host,
                   rows=len(rows), chips=cell.chips, peaks=peaks,
                   macs_per_image=macs_per_image(cfg),
                   eval_images=traffic["eval_images"],
                   lut_calls=lut_calls(cfg, traffic, programs, k),
                   host=host)
    result_metrics, extra = {}, {}
    if trace_dir:
        from bench import trace as tr
        run.trace = tr.reduce(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        offset = anchor - run.trace.window[0] / 1e9
        extra["breakdown"] = breakdown(run.trace, host["spans"], offset)
        record.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        e2e = {"rows_per_s": len(rows) / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}

    # the reference runs once the program's state is freed
    library = sweep.library
    sweep.close()
    del sweep
    gc.collect()
    jax.clear_caches()
    refmod = importlib.import_module(f"bench.references.{cfg['family']}")
    t_ref = time.monotonic()
    reference = refmod.Reference(cfg, run_params(cfg), traffic["eval_images"],
                                 traffic["eval_batch"])

    def lut_of(name):
        nl = library.entry(name).netlist
        return refmod.netlist_lut(nl.n_i, nl.funcs, nl.in0, nl.in1,
                                  nl.outputs)

    picks = correct.sample(rows, traffic["check_rows"], seed)
    numbers = correct.compare(rows, picks, reference, lut_of)
    ok, checks = correct.judge(numbers, cell.limits)
    log(f"[bench] reference: {len(picks)} rows "
        f"{[rows[i][:2] for i in picks]} in "
        f"{time.monotonic() - t_ref:.1f} s")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return {"correct": ok, "attempted": len(rows), "failed": 0,
            "metrics": result_metrics, "device": record, **extra,
            "checks": checks}


def run_params(cfg: dict):
    """The configuration's weights, made again for the reference."""
    fam = importlib.import_module(f"bench.models.{cfg['family']}")
    return fam.make_params(cfg, ROOT)


if __name__ == "__main__":
    sys.exit(main())
