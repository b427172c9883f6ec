"""Population-parallel CGP engine on the device bitsim (DESIGN.md §2.9).

The legacy ``cgp.evolve`` loop simulates ONE candidate per
``Netlist.eval_words`` call; fitness evaluation dominates the search, so
library generation throughput is capped by per-candidate python
dispatch.  This engine makes the (1+λ) step *generational*: all λ
offspring mutate from the same parent and are scored together —
``engine="device"`` runs the whole population through ONE
``bitsim_pop_pallas`` program and reduces the search metric on device
(exact integer sums, finished in float64 on host, so scores are
bit-identical to the numpy engine and the two engines walk identical
search trajectories at a fixed seed).

``evolve_ladder`` fuses a whole ladder of e_max-targeted searches into
one generation-synchronous sweep: every rung contributes λ offspring to
a single fused population per generation, and the population axis can
be sharded across devices via ``launch/mesh.pop_sharding`` (shard_map
over the candidate axis; netlist slices split, input planes replicated).

Search/verify split: everything here scores candidates on the sampled
search planes; admission to a library re-verifies exhaustively
(``metrics.evaluate_errors``) exactly like the sequential engine.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import platform
from ..kernels.bitsim import bitsim_pop_pallas
from ..kernels.ops import split_planes64
from .cgp import (CgpParams, EvolvedCircuit, _Score, _score, mutate,
                  search_planes, unpack_values)
from .cost import evaluate_cost
from .metrics import (METRIC_NAMES, error_report_from_values,
                      evaluate_errors)
from .netlist import Netlist, stack_netlists, unpack_outputs

# metrics whose reduction runs on device with EXACT integer arithmetic
# (chunked int32 partial sums finished in float64 on host); the rest
# simulate on device and reduce on host from the transferred values.
DEVICE_METRICS = ("er", "mae", "wce")

# population counts are padded up to a multiple of this so the jit
# cache sees one shape per (netlist-geometry, λ-bucket) instead of one
# per population size.
POP_PAD = 8

# exact int32 chunked sums need diff < 2^n_o and chunk * 2^n_o < 2^31
_REDUCE_MAX_N_O = 24
# values transfer as uint32, so the device engine caps at 32 outputs
_DEVICE_MAX_N_O = 32


def _pop_values(out32: jax.Array, n_o: int) -> jax.Array:
    """(P, n_o, W32) uint32 output planes -> (P, 32*W32) uint32 values.

    Lane L bit k is vector 32*L + k (the ``split_planes64`` layout), so
    a plain reshape restores vector order; output bit b contributes
    2^b.  Accumulates plane by plane to avoid a (P, n_o, num) temp.
    """
    p, _, w32 = out32.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    vals = jnp.zeros((p, w32 * 32), dtype=jnp.uint32)
    for b in range(n_o):
        bits = ((out32[:, b, :, None] >> shifts)
                & jnp.uint32(1)).reshape(p, w32 * 32)
        vals = vals | (bits << jnp.uint32(b))
    return vals


def _values_core(funcs, in0, in1, outs, planes32, *, n_nodes, n_i, n_o,
                 interpret):
    out = bitsim_pop_pallas(funcs, in0, in1, outs, planes32,
                            n_nodes=n_nodes, n_i=n_i, n_o=n_o,
                            interpret=interpret)
    return _pop_values(out, n_o)


def _reduce_core(funcs, in0, in1, outs, planes32, exact_u32, *, n_nodes,
                 n_i, n_o, num, interpret):
    """Population sim + on-device error reduction.

    Returns (ne, wce, sums): per-candidate count of differing vectors,
    max |diff|, and chunked partial sums of |diff| — all EXACT int32
    (chunk size (2^31-1) >> n_o bounds every partial sum below 2^31),
    so the float64 host finish reproduces the numpy metric bit for bit.
    """
    vals = _values_core(funcs, in0, in1, outs, planes32, n_nodes=n_nodes,
                        n_i=n_i, n_o=n_o, interpret=interpret)
    numpad = vals.shape[1]
    valid = jnp.arange(numpad) < num
    diff = jnp.abs(vals.astype(jnp.int32) - exact_u32.astype(jnp.int32))
    diff = jnp.where(valid[None, :], diff, 0)
    ne = jnp.sum(diff != 0, axis=1, dtype=jnp.int32)
    wce = jnp.max(diff, axis=1)
    chunk = max(1, (2 ** 31 - 1) >> n_o)
    pad = (-numpad) % chunk
    diffp = jnp.pad(diff, ((0, 0), (0, pad)))
    sums = diffp.reshape(diff.shape[0], -1, chunk).sum(
        axis=2, dtype=jnp.int32)
    return ne, wce, sums


_device_reduce = jax.jit(
    _reduce_core,
    static_argnames=("n_nodes", "n_i", "n_o", "num", "interpret"))
_device_values = jax.jit(
    _values_core, static_argnames=("n_nodes", "n_i", "n_o", "interpret"))


@functools.lru_cache(maxsize=None)
def _sharded_reduce(mesh, axis, n_nodes, n_i, n_o, num, interpret):
    """shard_map'd ``_reduce_core``: candidate axis split across
    ``axis``, planes + exact values replicated on every device."""
    inner = functools.partial(_reduce_core, n_nodes=n_nodes, n_i=n_i,
                              n_o=n_o, num=num, interpret=interpret)
    return jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(None, None), P(None)),
        out_specs=(P(axis), P(axis), P(axis, None)),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_values(mesh, axis, n_nodes, n_i, n_o, interpret):
    inner = functools.partial(_values_core, n_nodes=n_nodes, n_i=n_i,
                              n_o=n_o, interpret=interpret)
    return jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(None, None)),
        out_specs=P(axis, None),
        check_vma=False))


class PopEvaluator:
    """Scores candidate *populations* against one exact oracle.

    engine='numpy'  — per-candidate ``Netlist.eval_words`` host loop
                      (the sequential baseline).
    engine='device' — ONE ``bitsim_pop_pallas`` program per call;
                      er/mae/wce reduce on device (bit-identical floats
                      to the numpy engine), other metrics reduce on
                      host from device-computed values.

    ``sharding`` (a ``launch/mesh.pop_sharding`` NamedSharding) splits
    the population axis across devices via shard_map; population sizes
    are padded to a multiple of lcm(POP_PAD, axis size).  Instrumented:
    ``n_scored`` candidates / ``n_calls`` evaluation calls.
    """

    def __init__(self, exact: Netlist, params: CgpParams,
                 engine: str = "numpy",
                 sharding: Optional[NamedSharding] = None,
                 interpret: Optional[bool] = None):
        if engine not in ("numpy", "device"):
            raise ValueError(f"unknown engine {engine!r} "
                             "(expected 'numpy' or 'device')")
        if params.metric not in METRIC_NAMES:
            raise ValueError(f"unknown metric {params.metric}")
        self.engine = engine
        self.metric = params.metric
        self.exact = exact
        self.n_i, self.n_o = exact.n_i, exact.n_o
        rng = np.random.default_rng(params.seed + 7919)
        self.planes64, self.num = search_planes(
            self.n_i, params.search_samples, rng)
        exact_planes = exact.eval_words(self.planes64)
        self.exact_vals = unpack_values(exact_planes, self.n_o, self.num)
        self.sharding = sharding
        self.n_scored = 0
        self.n_calls = 0
        if engine == "device":
            if self.n_o > _DEVICE_MAX_N_O:
                raise ValueError(
                    f"device engine caps at {_DEVICE_MAX_N_O} output "
                    f"bits (got {self.n_o}); use engine='numpy' for "
                    "wider circuits")
            self.interpret = platform.interpret() if interpret is None \
                else interpret
            self.planes32 = jnp.asarray(split_planes64(self.planes64))
            numpad = self.planes32.shape[1] * 32
            buf = np.zeros(numpad, dtype=np.uint32)
            buf[:self.num] = unpack_outputs(
                exact_planes, self.n_o, self.num).astype(np.uint32)
            self.exact_u32 = jnp.asarray(buf)

    # -- scoring --------------------------------------------------------
    def errors_of(self, pop: Sequence[Netlist]) -> np.ndarray:
        """(len(pop),) float64 of ``params.metric`` per candidate —
        identical values from both engines."""
        pop = list(pop)
        self.n_scored += len(pop)
        self.n_calls += 1
        if self.engine == "numpy":
            out = np.empty(len(pop), dtype=np.float64)
            for k, nl in enumerate(pop):
                vals = unpack_values(nl.eval_words(self.planes64),
                                     self.n_o, self.num)
                out[k] = error_report_from_values(
                    vals, self.exact_vals, exhaustive=False
                ).get(self.metric)
            return out
        return self._device_errors(pop)

    def _padded(self, pop: list):
        axis = None
        pad_to = POP_PAD
        if self.sharding is not None and len(self.sharding.spec) \
                and self.sharding.spec[0] is not None:
            axis = self.sharding.spec[0]
            pad_to = int(np.lcm(POP_PAD,
                                self.sharding.mesh.shape[axis]))
        pp = -(-len(pop) // pad_to) * pad_to
        return pop + [pop[0]] * (pp - len(pop)), axis

    def _device_errors(self, pop: list) -> np.ndarray:
        p = len(pop)
        pop_p, axis = self._padded(pop)
        funcs, in0, in1, outs = stack_netlists(pop_p)
        n_nodes = funcs.shape[1]
        arrs = (jnp.asarray(funcs), jnp.asarray(in0), jnp.asarray(in1),
                jnp.asarray(outs))
        if self.metric in DEVICE_METRICS and self.n_o <= _REDUCE_MAX_N_O:
            if axis is not None:
                fn = _sharded_reduce(self.sharding.mesh, axis, n_nodes,
                                     self.n_i, self.n_o, self.num,
                                     self.interpret)
                ne, wce, sums = fn(*arrs, self.planes32, self.exact_u32)
            else:
                ne, wce, sums = _device_reduce(
                    *arrs, self.planes32, self.exact_u32,
                    n_nodes=n_nodes, n_i=self.n_i, n_o=self.n_o,
                    num=self.num, interpret=self.interpret)
            ne, wce, sums = (np.asarray(ne), np.asarray(wce),
                             np.asarray(sums))
            if self.metric == "er":
                vals = ne.astype(np.float64) / self.num
            elif self.metric == "wce":
                vals = wce.astype(np.float64)
            else:   # mae: exact integer total, float64 division
                vals = (sums.astype(np.int64).sum(axis=1)
                        .astype(np.float64) / self.num)
            return vals[:p]
        # host-reduced fallback (mse/mre/wcre, or n_o in 25..32): the
        # simulation still runs as one device program.
        if axis is not None:
            fn = _sharded_values(self.sharding.mesh, axis, n_nodes,
                                 self.n_i, self.n_o, self.interpret)
            vals32 = np.asarray(fn(*arrs, self.planes32))
        else:
            vals32 = np.asarray(_device_values(
                *arrs, self.planes32, n_nodes=n_nodes, n_i=self.n_i,
                n_o=self.n_o, interpret=self.interpret))
        out = np.empty(p, dtype=np.float64)
        for k in range(p):
            v = vals32[k, :self.num].astype(np.float64)
            out[k] = error_report_from_values(
                v, self.exact_vals, exhaustive=False).get(self.metric)
        return out


# ----------------------------------------------------------------------
# Generational (1+λ) search
# ----------------------------------------------------------------------
def _select(scores: list) -> int:
    """Best offspring index; ties resolve to the lowest index so both
    engines (and any future parallel scorer) agree deterministically."""
    return min(range(len(scores)),
               key=lambda i: (scores[i].infeasible, scores[i].primary, i))


def evolve_pop(
    seed_netlist: Netlist,
    exact: Netlist,
    params: CgpParams,
    engine: str = "numpy",
    on_candidate: Optional[Callable[[Netlist, float, float], None]] = None,
    evaluator: Optional[PopEvaluator] = None,
    sharding: Optional[NamedSharding] = None,
) -> EvolvedCircuit:
    """Generational (1+λ) run: all λ offspring mutate from the SAME
    parent and score in one ``PopEvaluator`` call (one device program
    when engine='device').  NOTE the deliberate semantic difference
    from ``cgp.evolve``, whose offspring chain within a generation —
    the generational step is what makes population scoring possible.
    Fixed seed ⇒ identical result from both engines.
    """
    rng = np.random.default_rng(params.seed)
    ev = evaluator if evaluator is not None else \
        PopEvaluator(exact, params, engine=engine, sharding=sharding)
    parent = seed_netlist
    p_err = float(ev.errors_of([parent])[0])
    p_score = _score(p_err, evaluate_cost(parent).area,
                     params.e_min, params.e_max)
    best_feasible: Optional[Netlist] = \
        parent if p_score.infeasible == 0 else None

    for _gen in range(params.generations):
        children = [mutate(parent, rng, params.h)
                    for _ in range(params.lam)]
        errs = ev.errors_of(children)
        areas = [evaluate_cost(c).area for c in children]
        scores = [_score(float(errs[k]), areas[k], params.e_min,
                         params.e_max) for k in range(params.lam)]
        k = _select(scores)
        if scores[k] <= p_score:   # allow neutral drift
            improved = scores[k] < p_score
            parent, p_err, p_score = children[k], float(errs[k]), scores[k]
            if p_score.infeasible == 0:
                best_feasible = parent
                if improved and on_candidate is not None:
                    on_candidate(parent, p_err, areas[k])

    final = best_feasible if best_feasible is not None else seed_netlist
    final = final.compact()
    errors = evaluate_errors(final, exact)   # exhaustive re-verify
    cost = evaluate_cost(final)
    return EvolvedCircuit(netlist=final, errors=errors,
                          cost_area=cost.area, cost_power=cost.power)


@dataclass
class _Run:
    e_max: float
    rng: np.random.Generator
    parent: Netlist
    p_err: float
    p_score: _Score
    best_feasible: Optional[Netlist]


def evolve_ladder(
    seed_netlist: Netlist,
    exact: Netlist,
    e_max_ladder: Sequence[float],
    params: CgpParams,
    engine: str = "device",
    on_candidate: Optional[
        Callable[[int, Netlist, float, float], None]] = None,
    sharding: Optional[NamedSharding] = None,
    evaluator: Optional[PopEvaluator] = None,
) -> list:
    """The whole e_max ladder as ONE generation-synchronous sweep.

    Every rung runs an independent generational (1+λ) search from the
    shared seed; per generation all rungs' offspring fuse into a single
    (len(ladder) * λ) population scored in one evaluator call — the
    population axis shards across devices via
    ``launch/mesh.pop_sharding``.  Rung i is trajectory-identical to
    ``evolve_pop(seed, exact, replace(params, e_max=ladder[i],
    seed=params.seed + i), evaluator=<shared>)``.

    ``on_candidate(rung_index, netlist, err, area)`` fires for every
    improved feasible parent.  Returns one ``EvolvedCircuit`` per rung
    (ladder sorted ascending), each exhaustively re-verified.
    """
    ladder = sorted(float(e) for e in e_max_ladder)
    ev = evaluator if evaluator is not None else \
        PopEvaluator(exact, params, engine=engine, sharding=sharding)
    seed_err = float(ev.errors_of([seed_netlist])[0])
    seed_area = evaluate_cost(seed_netlist).area
    runs = []
    for i, e_max in enumerate(ladder):
        sc = _score(seed_err, seed_area, params.e_min, e_max)
        runs.append(_Run(
            e_max=e_max, rng=np.random.default_rng(params.seed + i),
            parent=seed_netlist, p_err=seed_err, p_score=sc,
            best_feasible=seed_netlist if sc.infeasible == 0 else None))

    lam = params.lam
    for _gen in range(params.generations):
        pop = [mutate(r.parent, r.rng, params.h)
               for r in runs for _ in range(lam)]
        errs = ev.errors_of(pop)
        for ri, r in enumerate(runs):
            ch = pop[ri * lam:(ri + 1) * lam]
            es = errs[ri * lam:(ri + 1) * lam]
            areas = [evaluate_cost(c).area for c in ch]
            scores = [_score(float(es[k]), areas[k], params.e_min,
                             r.e_max) for k in range(lam)]
            k = _select(scores)
            if scores[k] <= r.p_score:
                improved = scores[k] < r.p_score
                r.parent, r.p_err, r.p_score = \
                    ch[k], float(es[k]), scores[k]
                if r.p_score.infeasible == 0:
                    r.best_feasible = r.parent
                    if improved and on_candidate is not None:
                        on_candidate(ri, r.parent, r.p_err, areas[k])

    out = []
    for r in runs:
        final = (r.best_feasible if r.best_feasible is not None
                 else seed_netlist).compact()
        errors = evaluate_errors(final, exact)   # exhaustive re-verify
        cost = evaluate_cost(final)
        out.append(EvolvedCircuit(netlist=final, errors=errors,
                                  cost_area=cost.area,
                                  cost_power=cost.power))
    return out
