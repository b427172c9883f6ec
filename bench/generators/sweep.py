"""The banked resilience sweep as traffic: a library screened bank by bank.

A traffic mix (``bench/traffic/<name>.json``) gives:

* ``library``, ``multiplier_prefix``: the library budget the program
  builds from its own seed, and which of its entries are screened;
* ``bank_lanes``: multipliers per bank; the banks cut the library in
  the run's order, wrapping around;
* ``mode``, ``variant``, ``per_layer``, ``quality_bound``: the
  arguments of ``repro.approx.dse.explore`` (``per_layer`` false is
  the Table II sweep alone, true adds the Fig. 4 sweep);
* ``eval_images``, ``eval_batch``: the eval set of every row;
* ``check_rows``: rows the comparison with the reference samples.

The run's seed fixes only the order in which the library's multipliers
enter the banks.  Every seed runs the same programs on banks of the
same size: the program embeds the weights and eval images in its
compiled code, so a seed that changed them would compile every program
anew in every run.

Each bank is one ``explore(..., batch=True)`` call with a fresh row
cache that holds only the golden baseline, so every row of the bank is
evaluated; the call returns when the rows are on the host.
"""
from __future__ import annotations

import importlib

import numpy as np


class Sweep:
    def __init__(self, cfg: dict, traffic: dict, seed: int, root: str):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.root = root
        self.family = importlib.import_module(f"bench.models.{cfg['family']}")

    def setup(self) -> None:
        """Library, weights, the program's workload, the golden
        baseline and one warm bank, which compiles or loads every
        program the window runs."""
        from repro.approx.dse import explore
        from repro.core.library import build_default_library

        t = self.traffic
        self.library = build_default_library(t["library"])
        names = [n for n in self.library.entries
                 if n.startswith(t["multiplier_prefix"])]
        rng = np.random.default_rng(self.seed)
        self.order = [names[i] for i in rng.permutation(len(names))]
        self.params = self.family.make_params(self.cfg, self.root)
        self.workload = self.family.program_workload(
            self.cfg, self.params, t["eval_images"], t["eval_batch"])
        self.golden_cache: dict = {}
        explore(self.workload, self.workload.layer_counts, self.library,
                multipliers=self.bank(0), all_layers=False, per_layer=False,
                cache=self.golden_cache)
        self.run_bank(0)

    def bank(self, k: int) -> list[str]:
        n = self.traffic["bank_lanes"]
        return [self.order[(k * n + i) % len(self.order)] for i in range(n)]

    def programs_per_bank(self) -> list:
        """The layer each banked program sweeps (None: all layers)."""
        layers = list(self.workload.layer_counts)
        return [None] + (layers if self.traffic["per_layer"] else [])

    def run_bank(self, k: int) -> list[tuple[str, str, dict]]:
        """Rows ``(multiplier, layer or "all", metrics)`` of bank ``k``."""
        from repro.approx.dse import explore

        t = self.traffic
        res = explore(self.workload, self.workload.layer_counts,
                      self.library, multipliers=self.bank(k), mode=t["mode"],
                      variant=t["variant"], batch=True,
                      per_layer=t["per_layer"],
                      quality_bound=t["quality_bound"],
                      cache=dict(self.golden_cache))
        return [(p.multiplier, p.layer, dict(p.metrics))
                for p in res.all_layers + res.per_layer]

    def close(self) -> None:
        """Drop the program's state before the reference runs."""
        del self.workload, self.params, self.golden_cache
