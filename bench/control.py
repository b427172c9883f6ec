#!/usr/bin/env python3
"""Readings the correctness limits are set from, for one cell, on the chip.

    python3 bench/control.py --workload resnet8_cifar.table2 \\
        --seeds 12 --banks 3 --out chiprun_out/control

In one process: the program sweeps the cell's library at the cell's own
size through its timed path (``explore`` on banks of the mix's width);
then, for each of ``--seeds`` seeds, the rows a run with that seed would
complete in ``--banks`` banks are sampled as ``bench/correct.py``
samples them, and two readings are taken of the compared numbers:

* the program's, against the float32 reference (the lower reading);
* the control's: the reference computed in bfloat16 put in the
  program's place, against the float32 reference (the upper reading).

Reference rows are computed once per (multiplier, layer) and shared by
the seeds.  Writes ``<out>/<cell>.json`` with every reading and prints
the largest program reading and the smallest control reading of each
number.  Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--banks", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "control"))
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax.numpy as jnp
    import numpy as np

    from bench import cells, correct, device
    from bench.generators.sweep import Sweep
    from repro.launch.compile_cache import enable_compile_cache

    cell = cells.Cell(args.workload)
    device.require_tpu(cell.chips)
    enable_compile_cache(os.path.join(ROOT, ".jax_cache_bench"))
    cfg, traffic = cell.config, cell.traffic
    t0 = time.monotonic()
    sweep = Sweep(cfg, traffic, 0, ROOT)
    sweep.setup()
    n_lanes = traffic["bank_lanes"]
    banks = -(-len(sweep.order) // n_lanes)
    program = {}
    for k in range(banks):
        for mult, layer, met in sweep.run_bank(k):
            program[(mult, layer)] = met
    library, names = sweep.library, list(sweep.order)
    layers = [None] + (list(sweep.workload.layer_counts)
                       if traffic["per_layer"] else [])
    sweep.close()
    print(f"[control] program: {len(program)} rows in "
          f"{time.monotonic() - t0:.1f} s", flush=True)

    refmod = importlib.import_module(f"bench.references.{cfg['family']}")
    params = sweep.family.make_params(cfg, ROOT)
    n, b = traffic["eval_images"], traffic["eval_batch"]
    ref = refmod.Reference(cfg, params, n, b)
    ctl = refmod.Reference(cfg, params, n, b, dtype=jnp.bfloat16)
    luts: dict = {}

    def lut_of(name):
        if name not in luts:
            nl = library.entry(name).netlist
            luts[name] = refmod.netlist_lut(nl.n_i, nl.funcs, nl.in0,
                                            nl.in1, nl.outputs)
        return luts[name]

    ref_rows: dict = {}
    ctl_rows: dict = {}

    readings = []
    for s in range(args.first_seed, args.first_seed + args.seeds):
        order = [names[i] for i in
                 np.random.default_rng(s).permutation(len(names))]
        rows = []
        for k in range(args.banks):
            bank = [order[(k * n_lanes + i) % len(order)]
                    for i in range(n_lanes)]
            for layer in layers:
                key = "all" if layer is None else layer
                rows += [(m, key, program[(m, key)]) for m in bank]
        picks = correct.sample(rows, traffic["check_rows"], s)
        for i in picks:
            m, layer, _ = rows[i]
            if (m, layer) not in ref_rows:
                t = time.monotonic()
                ref_rows[(m, layer)] = ref.row(lut_of(m), layer)
                ctl_rows[(m, layer)] = ctl.row(lut_of(m), layer)
                print(f"[control] reference row {m} {layer}: "
                      f"{time.monotonic() - t:.1f} s", flush=True)
        got = _compare(rows, picks, ref, ref_rows, correct)
        ctl_view = [(m, layer, ctl_rows.get((m, layer))) for m, layer, _
                    in rows]
        low = _compare(ctl_view, picks, ref, ref_rows, correct)
        readings.append({"seed": s, "picks": [rows[i][:2] for i in picks],
                         "program": got, "control": low})
        print(f"[control] seed {s}: program {got} control {low}",
              flush=True)

    summary = {
        "cell": args.workload,
        "lower": {k: max(r["program"][k] for r in readings)
                  for k in readings[0]["program"]},
        "upper": {k: min(r["control"][k] for r in readings)
                  for k in readings[0]["control"]},
        "readings": readings,
        "rows": {f"{m}|{l}": {"program": program[(m, l)],
                              "reference": ref_rows[(m, l)],
                              "control": ctl_rows[(m, l)]}
                 for (m, l) in ref_rows},
        "device": device.record(device.require_tpu(cell.chips)),
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"lower": summary["lower"],
                      "upper": summary["upper"]}), flush=True)
    return 0


def _compare(rows, picks, ref, memo, correct):
    """``correct.compare`` with the reference's rows taken from
    ``memo``."""
    class Memo:
        def __init__(self):
            self.i = 0

        def row(self, lut, layer):
            m, l, _ = rows[picks[self.i]]
            self.i += 1
            return memo[(m, l)]

        logit_scale = staticmethod(ref.logit_scale)

    return correct.compare(rows, picks, Memo(), lambda name: None)


if __name__ == "__main__":
    sys.exit(main())
