"""Resilience analysis driver (paper Sec. IV, Fig. 4 and Table II).

Given an evaluation handle — a ``repro.approx.workload.Workload``, a
``BankableEval``, or a plain ``eval_fn(policy) -> accuracy`` closure
(all normalized through ``as_workload``, DESIGN.md §2.7) — and the
model's per-layer multiplication counts, sweeps approximate multipliers
  * one layer at a time (Fig. 4 — layer sensitivity), and
  * across all layers at once (Table II — accuracy vs. power trade-off),
reporting classification accuracy together with the network-level
relative multiplier power.  The non-swept layers use the exact int8
datapath, the paper's golden reference.

Backends are built spec-first: each multiplier name becomes a
``BackendSpec`` materialized once against the library, so every policy
the sweep evaluates shares the same backend objects (one jit trace per
multiplier instead of one per policy instance).

Both sweeps also run **batched** (``batch=True``): the multiplier axis
is packed into a ``LutBank`` and evaluated under ``jax.vmap`` in O(1)
compiled programs per sweep (one for all-layers, one per layer for
per-layer) instead of O(n_mult) traces — bit-identical accuracies to
the sequential path (DESIGN.md §2.4).  Batching requires a traceable
evaluation function; wrap yours in ``BankableEval``.  The ``explore()``
facade in ``repro.approx.dse`` wraps both sweeps with result caching
and Pareto selection.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ..tracing import span
from .backend import BackendLike
from .layers import ApproxPolicy, bank_eval
from .power import (auto_rel_power, cost_axes_map,
                    network_costs_for_assignment,
                    network_power_for_assignment)
from .registry import get_datapath
from .specs import BackendSpec, MaterializedBackend, packed_bank
from .workload import Workload, as_workload


@dataclass
class ResilienceRow:
    """One sweep measurement.  ``metrics`` carries EVERY named quality
    metric the workload measured (DESIGN.md §2.7); ``accuracy`` is the
    legacy scalar alias for the workload's PRIMARY metric (named for
    the paper's classification case — it holds e.g. a logit-MAE for
    fidelity workloads).  ``costs`` carries the library-derived
    area/delay axes next to the power columns."""

    multiplier: str
    layer: str                 # layer name or "all"
    accuracy: float            # = metrics[workload.primary]
    network_rel_power: float   # count-weighted multiplier power
    multiplier_rel_power: float
    mult_share: float          # fraction of network mults in this layer
    errors: dict = field(default_factory=dict)
    spec: Optional[BackendSpec] = None
    metrics: dict = field(default_factory=dict)
    costs: dict = field(default_factory=dict)


@dataclass
class BankableEval:
    """An evaluation function in both calling conventions the sweeps
    understand.  Subsumed by ``repro.approx.workload.Workload`` (the
    multi-metric generalization, DESIGN.md §2.7) — the sweeps
    normalize either through ``as_workload``; BankableEval remains the
    lightest way to hand over a single scalar accuracy.

    ``fn(policy) -> float`` is the sequential closure (free to jit
    internally, call numpy, return a Python float).  ``traceable`` is
    its pure-jax core — arrays in, a scalar accuracy array out, no
    side effects — which the batched engine wraps in ``jit(vmap(...))``
    over the multiplier bank.  The two must compute the same number for
    the same policy; the batched path is then bit-identical to the
    sequential one by construction.  Calling the object delegates to
    ``fn``, so a ``BankableEval`` drops into every sequential call site
    unchanged.
    """

    fn: Callable[[ApproxPolicy], float]
    traceable: Callable[[ApproxPolicy], "object"]

    def __call__(self, policy: ApproxPolicy) -> float:
        return self.fn(policy)


def can_bank(eval_fn, mode: str, variant: str = "ref") -> bool:
    """True when ``(eval_fn, mode, variant)`` supports the batched
    engine: the eval exposes a traceable core and the datapath declares
    ``bankable`` (lut-family; lowrank/int8 do not bank)."""
    if getattr(eval_fn, "traceable", None) is None:
        return False
    name = mode if variant == "ref" else f"{mode}_{variant}"
    try:
        return bool(get_datapath(name).bankable)
    except KeyError:
        return False


def _backends_for(multiplier_names, library, mode: str, rank=None,
                  variant: str = "ref") -> dict[str, MaterializedBackend]:
    out = {}
    for name in multiplier_names:
        spec = BackendSpec(mode=mode, multiplier=name, rank=rank,
                           variant=variant)
        out[name] = spec.materialize(library)
    return out


def _row(library, mname, layer, metrics, primary, layer_counts, spec,
         rel_power=None, cost_map=None) -> ResilienceRow:
    entry = library.entry(mname)
    # rel_power overrides rebase power onto a common reference for
    # mixed-width sweeps (power.rel_power_map, DESIGN.md §2.6); the
    # default is the library's same-width convention
    rp = (rel_power[mname] if rel_power is not None
          else entry.rel_power)
    acc = float(metrics[primary])
    total = sum(layer_counts.values())
    if layer == "all":
        assignment = {l: mname for l in layer_counts}
        return ResilienceRow(
            multiplier=mname, layer="all", accuracy=acc,
            network_rel_power=rp,
            multiplier_rel_power=rp,
            mult_share=1.0, errors=entry.errors.as_dict(), spec=spec,
            metrics=dict(metrics),
            costs=(network_costs_for_assignment(layer_counts, assignment,
                                                cost_map)
                   if cost_map is not None else {}))
    # a per-layer row is the one-layer special case of a heterogeneous
    # assignment; both score power (and area/delay) through the same
    # component model
    return ResilienceRow(
        multiplier=mname, layer=layer, accuracy=acc,
        network_rel_power=network_power_for_assignment(
            layer_counts, {layer: mname}, {mname: rp}),
        multiplier_rel_power=rp,
        mult_share=layer_counts[layer] / total,
        errors=entry.errors.as_dict(), spec=spec,
        metrics=dict(metrics),
        costs=(network_costs_for_assignment(layer_counts, {layer: mname},
                                            cost_map)
               if cost_map is not None else {}))


# ----------------------------------------------------------------------
# Per-layer component models (autoAx-style, DESIGN.md §2.5)
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class LayerComponents:
    """Per-layer quality/power component models distilled from the
    Fig. 4 per-layer sweep rows — the prediction stage of the two-stage
    heterogeneous DSE (autoAx: compose per-layer measurements into
    network-level estimates, then verify the shortlist exactly).

    ``quality[j, i]`` is the measured network accuracy with ONLY layer
    ``layers[j]`` running multiplier ``multipliers[i]`` (everything else
    golden int8); ``rel_power[i]`` is the multiplier's relative power.
    The composition model is additive in accuracy *drops* (clipped at
    zero: measurement noise must not predict improvements) and exact in
    power (count-weighted mean, the same arithmetic the verified points
    report).
    """

    layers: tuple[str, ...]
    multipliers: tuple[str, ...]
    quality: "np.ndarray"           # (n_layers, n_mult) accuracies
    rel_power: "np.ndarray"         # (n_mult,)
    counts: tuple[int, ...]         # per layers[j] mult counts
    total_count: int                # whole-network mult count
    baseline: float                 # golden int8 accuracy
    direction: str = "max"          # primary metric direction (§2.7):
                                    # "min" primaries (logit MAE,
                                    # perplexity) flip the drop sign

    @staticmethod
    def from_rows(rows: "list[ResilienceRow]", layer_counts: dict,
                  baseline: float,
                  direction: str = "max") -> "LayerComponents":
        """Distill per-layer sweep rows (any order, any coverage) into
        component matrices.  Missing (layer, multiplier) cells fall back
        to the baseline accuracy (no measured evidence of damage)."""
        layers = tuple(dict.fromkeys(
            r.layer for r in rows if r.layer != "all"))
        mults = tuple(dict.fromkeys(
            r.multiplier for r in rows if r.layer != "all"))
        li = {l: j for j, l in enumerate(layers)}
        mi = {m: i for i, m in enumerate(mults)}
        quality = np.full((len(layers), len(mults)), baseline)
        rel_power = np.ones(len(mults))
        for r in rows:
            if r.layer == "all":
                continue
            quality[li[r.layer], mi[r.multiplier]] = r.accuracy
            rel_power[mi[r.multiplier]] = r.multiplier_rel_power
        return LayerComponents(
            layers=layers, multipliers=mults, quality=quality,
            rel_power=rel_power,
            counts=tuple(int(layer_counts[l]) for l in layers),
            total_count=int(sum(layer_counts.values())),
            baseline=float(baseline), direction=direction)

    def drop(self) -> "np.ndarray":
        """(n_layers, n_mult) per-layer quality DEGRADATIONS, clipped
        >= 0 — baseline − quality for maximize primaries, quality −
        baseline for minimize ones (a fidelity workload's MAE *rises*
        under approximation)."""
        if self.direction == "min":
            return np.maximum(self.quality - self.baseline, 0.0)
        return np.maximum(self.baseline - self.quality, 0.0)

    def predict_accuracy(self, assign: "np.ndarray") -> float:
        """Additive-drop estimate of the primary metric for one
        assignment row (indices into ``multipliers``)."""
        d = self.drop()
        total = float(sum(d[j, i] for j, i in enumerate(assign)))
        return (self.baseline + total if self.direction == "min"
                else self.baseline - total)

    def predict_power(self, assign: "np.ndarray") -> float:
        """Exact count-weighted power of one assignment row (layers
        outside ``layers`` are golden int8 at rel power 1.0)."""
        assigned = sum(c * self.rel_power[i]
                       for c, i in zip(self.counts, assign))
        rest = self.total_count - sum(self.counts)
        if self.total_count == 0:
            return 1.0
        return float((assigned + rest) / self.total_count)

    def layer_pareto(self) -> list[list[int]]:
        """Per layer: multiplier indices non-dominated on
        (accuracy-drop min, power min) — the layer-wise pruning stage.
        Candidates are returned sorted by ascending power."""
        d = self.drop()
        fronts = []
        for j in range(len(self.layers)):
            order = sorted(range(len(self.multipliers)),
                           key=lambda i: (self.rel_power[i], d[j, i]))
            front: list[int] = []
            best = float("inf")
            for i in order:
                if d[j, i] < best:
                    front.append(i)
                    best = d[j, i]
            fronts.append(front)
        return fronts


def per_layer_sweep(
    eval_fn: Callable[[ApproxPolicy], float],
    layer_counts: dict[str, int],
    multiplier_names: list[str],
    library,
    mode: str = "lut",
    base: Optional[BackendLike] = None,
    variant: str = "ref",
    batch: bool = False,
    sharding=None,
    rel_power=None,
) -> list[ResilienceRow]:
    """Fig. 4: one layer approximated at a time.

    Sequential (default): one ``eval_fn`` call — and typically one jit
    trace — per (layer, multiplier) pair.  Batched (``batch=True``,
    requires a ``BankableEval``): the multiplier axis is packed into a
    ``LutBank`` and each layer evaluates ALL candidates in one compiled
    program — O(n_layers) programs total instead of
    O(n_layers * n_mult).  Accuracies are bit-identical between the two
    paths; ``sharding`` optionally spreads the bank axis across devices
    (``repro.launch.mesh.bank_sharding``).

    ``multiplier_names`` may MIX operand widths (8-bit entries next to
    composed 12/16-bit ones, DESIGN.md §2.6) — the bank stays one
    compiled program per layer either way, and power is auto-rebased
    onto a common reference (``power.auto_rel_power``) unless an
    explicit ``rel_power`` map is given.
    """
    base = base if base is not None else BackendSpec.golden().materialize()
    wl, rel_power, cost_map, backends, bank = _prepare(
        eval_fn, multiplier_names, library, mode, variant, batch,
        rel_power)
    rows = []
    if batch:
        for layer in layer_counts:
            lanes = _unstack_metrics(
                bank_eval(wl.traceable_metrics, bank, mode=mode,
                          variant=variant, base=base,
                          layer_pattern=layer, sharding=sharding),
                wl.metrics, len(multiplier_names))
            with span("sweep.rows"):
                for mname, metrics in zip(multiplier_names, lanes):
                    rows.append(_row(library, mname, layer, metrics,
                                     wl.primary, layer_counts,
                                     backends[mname].spec, rel_power,
                                     cost_map))
        return rows
    for layer in layer_counts:
        for mname, be in backends.items():
            policy = ApproxPolicy(default=base, overrides=[(layer, be)])
            rows.append(_row(library, mname, layer, wl.measure(policy),
                             wl.primary, layer_counts, be.spec,
                             rel_power, cost_map))
    return rows


def all_layers_sweep(
    eval_fn: Callable[[ApproxPolicy], float],
    layer_counts: dict[str, int],
    multiplier_names: list[str],
    library,
    mode: str = "lut",
    variant: str = "ref",
    batch: bool = False,
    sharding=None,
    rel_power=None,
) -> list[ResilienceRow]:
    """Table II: the same multiplier in every (conv) layer.

    Sequential (default): one ``eval_fn`` call per multiplier.  Batched
    (``batch=True``, requires a ``BankableEval``): ONE compiled program
    evaluates the whole ``LutBank`` — O(1) traces/compiles regardless
    of ``len(multiplier_names)``, bit-identical accuracies to the
    sequential path.  ``sharding`` optionally spreads the bank axis
    across devices.

    Width-generic: mixed 8/12/16-bit candidate sets bank into the same
    O(1) program (per-lane widths ride the vmapped axis, DESIGN.md
    §2.6), with power auto-rebased onto a common reference
    (``power.auto_rel_power``) unless ``rel_power`` overrides it.
    """
    wl, rel_power, cost_map, backends, bank = _prepare(
        eval_fn, multiplier_names, library, mode, variant, batch,
        rel_power)
    if batch:
        lanes = _unstack_metrics(
            bank_eval(wl.traceable_metrics, bank, mode=mode,
                      variant=variant, sharding=sharding),
            wl.metrics, len(multiplier_names))
        with span("sweep.rows"):
            return [_row(library, mname, "all", metrics, wl.primary,
                         layer_counts, backends[mname].spec, rel_power,
                         cost_map)
                    for mname, metrics in zip(multiplier_names, lanes)]
    rows = []
    for mname, be in backends.items():
        policy = ApproxPolicy(default=be)
        rows.append(_row(library, mname, "all", wl.measure(policy),
                         wl.primary, layer_counts, be.spec, rel_power,
                         cost_map))
    return rows


def _prepare(eval_fn, multiplier_names, library, mode: str, variant: str,
             batch: bool, rel_power):
    """What a sweep needs before it evaluates, as the span
    ``sweep.prep``: ``(workload, rel_power, cost_map, backends, bank)``,
    the bank None unless ``batch``.  The span's ``built`` says whether
    the bank was packed or came from ``bank_for``'s cache."""
    with span("sweep.prep") as attrs:
        wl = as_workload(eval_fn)
        if rel_power is None:
            rel_power = auto_rel_power(library, multiplier_names)
        cost_map = cost_axes_map(library, multiplier_names)
        backends = _backends_for(multiplier_names, library, mode,
                                 variant=variant)
        bank = None
        if batch:
            wl = _require_bankable(wl, mode, variant)
            bank, attrs["built"] = packed_bank(multiplier_names, library)
    return wl, rel_power, cost_map, backends, bank


def _unstack_metrics(out, metric_names, n: int) -> list[dict]:
    """Split a banked evaluation's stacked metric dict ``{metric:
    (n,) array}`` into one float dict per lane, in workload metric
    order.  The copy to the host, where the host waits for the device,
    is the span ``bank_eval.wait``."""
    with span("bank_eval.wait"):
        arrs = {m: np.asarray(out[m]) for m in metric_names}
    return [{m: float(arrs[m][i]) for m in metric_names}
            for i in range(n)]


def _require_bankable(eval_fn, mode: str, variant: str) -> Workload:
    wl = as_workload(eval_fn)
    if not can_bank(wl, mode, variant):
        raise ValueError(
            "batch=True needs a bank-traceable evaluation (a Workload "
            "with traceable_metrics, or a BankableEval) and a bankable "
            f"datapath; got {type(eval_fn).__name__} with mode={mode!r} "
            f"variant={variant!r}.  Wrap your eval in "
            "BankableEval/Workload or use explore(batch=True), which "
            "falls back to the sequential path.")
    return wl
