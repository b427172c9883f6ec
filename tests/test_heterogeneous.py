"""Heterogeneous per-layer composition (DESIGN.md §2.5): PolicyBank,
policy_bank_eval bit-identity + O(1) traces, component models, the
two-stage explore_heterogeneous, and heterogeneous policy round-trips
through JSON / checkpoints."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.dse import (DesignPoint, compose_assignments,
                              explore_heterogeneous, verify_assignments)
from repro.approx.layers import (ApproxPolicy, policy_bank_eval,
                                 policy_for_lane)
from repro.approx.power import (LayerPower, network_power_for_assignment,
                                per_layer_share)
from repro.approx.resilience import BankableEval, LayerComponents
from repro.approx.specs import BackendSpec, PolicyBank
from repro.core.library import build_default_library

MULTS = ["mul8u_exact", "mul8u_trunc4", "mul8u_trunc2"]
LAYERS = ("lin_a", "lin_b")
COUNTS = {"lin_a": 100, "lin_b": 300}


@pytest.fixture(scope="module")
def lib():
    return build_default_library("tiny")


@pytest.fixture(scope="module")
def toy_eval():
    """Two-matmul toy network with a traceable core instrumented to
    count jax traces (runs once per trace, not per policy)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w_a = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    w_b = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    traces = []

    def traceable(policy):
        traces.append(1)
        y = policy.matmul("lin_a", x, w_a)
        y = policy.matmul("lin_b", jax.nn.relu(y), w_b)
        return jnp.mean(y)

    def fn(policy):
        return float(jax.jit(lambda: traceable(policy))())

    return BankableEval(fn=fn, traceable=traceable), traces


def _random_bank(lib, n_policies=5, seed=0) -> PolicyBank:
    rng = np.random.default_rng(seed)
    assignments = [{l: MULTS[rng.integers(0, len(MULTS))] for l in LAYERS}
                   for _ in range(n_policies)]
    return PolicyBank.from_assignments(assignments, lib, layers=LAYERS)


# ----------------------------------------------------------------------
# PolicyBank construction
# ----------------------------------------------------------------------
def test_policy_bank_construction_and_validation(lib):
    pb = PolicyBank.from_assignments(
        [{"lin_a": "mul8u_trunc4", "lin_b": "mul8u_exact"},
         {"lin_a": "mul8u_trunc2", "lin_b": "mul8u_trunc4"}], lib)
    assert pb.n_policies == 2 and pb.n_layers == 2
    assert pb.layers == ("lin_a", "lin_b")
    # dedup: three distinct multipliers across 4 cells
    assert sorted(pb.bank.names) == sorted(MULTS)
    assert pb.assignment(0) == {"lin_a": "mul8u_trunc4",
                                "lin_b": "mul8u_exact"}
    with pytest.raises(ValueError, match="misses"):
        PolicyBank.from_assignments([{"lin_a": "mul8u_exact"}], lib,
                                    layers=LAYERS)
    with pytest.raises(ValueError, match="assign"):
        PolicyBank(bank=pb.bank, layers=LAYERS,
                   assign=np.zeros((2, 3), np.int32))
    with pytest.raises(ValueError, match="indices"):
        PolicyBank(bank=pb.bank, layers=LAYERS,
                   assign=np.full((1, 2), 99, np.int32))


def test_policy_bank_uniform_rows(lib):
    pb = PolicyBank.uniform(MULTS, LAYERS, lib)
    assert pb.n_policies == len(MULTS)
    for p, name in enumerate(MULTS):
        assert set(pb.assignment(p).values()) == {name}


# ----------------------------------------------------------------------
# The engine contract: bit-identity + O(1) compiled programs
# ----------------------------------------------------------------------
def test_policy_bank_eval_bit_identical_to_sequential(lib, toy_eval):
    eval_fn, traces = toy_eval
    pb = _random_bank(lib)
    traces.clear()
    batched = np.asarray(policy_bank_eval(eval_fn.traceable, pb,
                                          mode="lut"))
    assert len(traces) == 1, "K policies must compile O(1) programs"
    seq = np.asarray(
        [eval_fn(policy_for_lane(pb, p).materialize(lib))
         for p in range(pb.n_policies)], dtype=batched.dtype)
    np.testing.assert_array_equal(batched, seq)


def test_policy_bank_eval_pallas_variant_bit_identical(lib, toy_eval):
    eval_fn, _ = toy_eval
    pb = _random_bank(lib, n_policies=3, seed=1)
    batched = np.asarray(policy_bank_eval(eval_fn.traceable, pb,
                                          mode="lut", variant="pallas"))
    seq = np.asarray(
        [eval_fn(policy_for_lane(pb, p, variant="pallas").materialize(lib))
         for p in range(pb.n_policies)], dtype=batched.dtype)
    np.testing.assert_array_equal(batched, seq)


def test_policy_bank_eval_sharded_matches_unsharded(lib, toy_eval):
    from repro.launch.mesh import policy_sharding, sweep_mesh
    eval_fn, _ = toy_eval
    pb = _random_bank(lib, n_policies=4, seed=2)
    got = np.asarray(policy_bank_eval(
        eval_fn.traceable, pb,
        assign_sharding=policy_sharding(pb.n_policies, sweep_mesh())))
    want = np.asarray(policy_bank_eval(eval_fn.traceable, pb))
    np.testing.assert_array_equal(got, want)


def test_verify_assignments_batched_equals_sequential(lib, toy_eval):
    eval_fn, _ = toy_eval
    assignments = [{"lin_a": "mul8u_trunc4", "lin_b": "mul8u_exact"},
                   {"lin_a": "mul8u_trunc2", "lin_b": "mul8u_trunc4"}]
    bat = verify_assignments(eval_fn, assignments, COUNTS, lib,
                             batch=True)
    seq = verify_assignments(eval_fn, assignments, COUNTS, lib,
                             batch=False)
    assert [p.accuracy for p in bat] == [p.accuracy for p in seq]
    assert [p.network_rel_power for p in bat] == \
        [p.network_rel_power for p in seq]
    assert [p.assignment for p in bat] == [p.assignment for p in seq]


# ----------------------------------------------------------------------
# Heterogeneous policy serialization
# ----------------------------------------------------------------------
def test_heterogeneous_policy_json_round_trip_preserves_ordering(lib):
    overrides = [("lin_b", BackendSpec(mode="lut",
                                       multiplier="mul8u_trunc4")),
                 ("lin_a", BackendSpec(mode="lut",
                                       multiplier="mul8u_trunc2")),
                 ("lin_*", BackendSpec(mode="lut",
                                       multiplier="mul8u_exact"))]
    pol = ApproxPolicy(default=BackendSpec.golden(), overrides=overrides)
    rt = ApproxPolicy.from_json(pol.to_json())
    # ordering is semantic (first match wins for overlapping patterns)
    assert [(p, spec_of_entry(be)) for p, be in rt.overrides] \
        == [(p, s) for p, s in overrides]
    assert rt.cache_key() == pol.cache_key()
    assert rt.backend_for("lin_a") == overrides[1][1]


def spec_of_entry(be):
    from repro.approx.layers import spec_of
    return spec_of(be)


def test_heterogeneous_policy_materialize_idempotent(lib):
    pb = _random_bank(lib, n_policies=1, seed=4)
    pol = policy_for_lane(pb, 0)
    m1 = pol.materialize(lib)
    m2 = m1.materialize(lib)
    # materializing a materialized policy changes nothing: same backend
    # objects (the cache guarantees identity), same cache key
    assert m2.cache_key() == m1.cache_key()
    for (p1, b1), (p2, b2) in zip(m1.overrides, m2.overrides):
        assert p1 == p2 and b1 is b2
    assert m1.default is m2.default


def test_heterogeneous_policy_ships_in_checkpoint_metadata(tmp_path, lib):
    from repro.train.checkpoint import CheckpointManager, \
        policy_from_metadata
    pb = _random_bank(lib, n_policies=1, seed=5)
    pol = policy_for_lane(pb, 0)
    mgr = CheckpointManager(str(tmp_path), keep=1)
    state = {"w": np.ones((2, 2), np.float32)}
    mgr.save(1, state, policy=pol)
    _, meta = mgr.restore(state)
    rt = policy_from_metadata(meta)
    assert rt is not None and rt.cache_key() == pol.cache_key()


def test_design_point_from_assignment_policy(lib):
    a = {"lin_a": "mul8u_trunc4", "lin_b": "mul8u_trunc2"}
    pt = DesignPoint.from_assignment(a, accuracy=0.9,
                                     network_rel_power=0.25)
    assert pt.layer == "hetero" and pt.multiplier == "hetero[2]"
    pol = pt.policy()
    assert [p for p, _ in pol.overrides] == list(a)
    assert pt.to_dict()["assignment"] == a
    # the policy reproduces the datapath the point was verified under
    pt_pallas = DesignPoint.from_assignment(a, 0.9, 0.25,
                                            variant="pallas")
    assert all(be.variant == "pallas"
               for _, be in pt_pallas.policy().overrides)
    uniform = DesignPoint.from_assignment(
        {"lin_a": "mul8u_trunc4", "lin_b": "mul8u_trunc4"}, 0.9, 0.2)
    assert uniform.multiplier == "mul8u_trunc4"


# ----------------------------------------------------------------------
# Component models + composition
# ----------------------------------------------------------------------
def _toy_components() -> LayerComponents:
    return LayerComponents(
        layers=LAYERS, multipliers=tuple(MULTS),
        quality=np.asarray([[0.9, 0.88, 0.6],     # lin_a tolerates trunc4
                            [0.9, 0.7, 0.5]]),    # lin_b only exact
        rel_power=np.asarray([1.0, 0.2, 0.02]),
        counts=(100, 300), total_count=400, baseline=0.9)


def test_layer_components_drop_and_power():
    c = _toy_components()
    d = c.drop()
    assert d[0, 0] == 0.0 and d[1, 1] == pytest.approx(0.2)
    # exact everywhere
    assert c.predict_power(np.asarray([0, 0])) == pytest.approx(1.0)
    assert c.predict_accuracy(np.asarray([0, 0])) == pytest.approx(0.9)
    # trunc4 in lin_a only: count-weighted power
    assert c.predict_power(np.asarray([1, 0])) == pytest.approx(
        (100 * 0.2 + 300 * 1.0) / 400)
    fronts = c.layer_pareto()
    # every multiplier is non-dominated in both layers here (cheaper is
    # always more damaged), sorted by ascending power
    assert fronts[0] == [2, 1, 0] and fronts[1] == [2, 1, 0]


def test_layer_components_from_rows_matches_power_model(lib, toy_eval):
    from repro.approx.resilience import per_layer_sweep
    eval_fn, _ = toy_eval
    rows = per_layer_sweep(eval_fn, COUNTS, MULTS, lib, mode="lut")
    c = LayerComponents.from_rows(rows, COUNTS, baseline=0.9)
    assert c.layers == tuple(COUNTS) and c.multipliers == tuple(MULTS)
    i = c.multipliers.index("mul8u_trunc4")
    rp = lib.entries["mul8u_trunc4"].rel_power
    assert c.rel_power[i] == pytest.approx(rp)
    # predict_power for a one-layer assignment equals the shared
    # assignment power model (and therefore the per-layer row's power)
    row = next(r for r in rows if r.multiplier == "mul8u_trunc4"
               and r.layer == "lin_a")
    assign = np.asarray([i, c.multipliers.index("mul8u_exact")])
    want = network_power_for_assignment(
        COUNTS, {"lin_a": "mul8u_trunc4", "lin_b": "mul8u_exact"},
        {"mul8u_trunc4": rp, "mul8u_exact": 1.0})
    assert c.predict_power(assign) == pytest.approx(want)
    assert row.network_rel_power == pytest.approx(
        network_power_for_assignment(COUNTS, {"lin_a": "mul8u_trunc4"},
                                     {"mul8u_trunc4": rp}))


def test_compose_assignments_respects_bound_and_budget():
    c = _toy_components()
    rows = compose_assignments(c, quality_bound=0.05, top_k=4)
    assert rows, "beam must return candidates"
    # within the bound's ladder no candidate may use trunc2 in lin_b
    # (drop 0.4 > 2x bound); the cheapest feasible uses trunc4 in lin_a
    for r in rows:
        assert c.multipliers[r[1]] != "mul8u_trunc2"
    best = rows[0]
    assert c.multipliers[best[0]] in ("mul8u_trunc4", "mul8u_trunc2")
    budget = compose_assignments(c, quality_bound=0.05,
                                 power_budget=0.5, top_k=4)
    assert all(c.predict_power(r) <= 0.5 for r in budget)


# ----------------------------------------------------------------------
# explore_heterogeneous end-to-end
# ----------------------------------------------------------------------
def test_explore_heterogeneous_end_to_end(lib, toy_eval):
    eval_fn, traces = toy_eval
    cache: dict = {}
    res = explore_heterogeneous(eval_fn, COUNTS, lib, multipliers=MULTS,
                                quality_bound=0.5, top_k=4, cache=cache)
    assert res.per_layer, "stage 1 fills the per-layer axis"
    assert res.heterogeneous, "stage 2 fills the heterogeneous axis"
    for p in res.heterogeneous:
        assert p.layer == "hetero" and p.assignment is not None
        assert set(dict(p.assignment)) == set(COUNTS)
    assert res.selected is not None
    assert res.selected.accuracy >= res.baseline_accuracy - 0.5
    # verified results were seeded into the cache under
    # sequential-compatible policy keys: re-verifying sequentially with
    # the cache runs zero extra evals
    calls = [0]

    def counting(policy):
        calls[0] += 1
        return 0.0

    verify_assignments(
        BankableEval(fn=counting, traceable=None),
        [dict(p.assignment) for p in res.heterogeneous],
        COUNTS, lib, batch=False, cache=cache)
    assert calls[0] == 0
    # combined selection + pareto axes are well-formed
    assert res.within(1.0, axis="combined")
    assert res.pareto(axis="heterogeneous")


def test_explore_heterogeneous_sequential_fallback(lib):
    calls = [0]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    w = jnp.asarray(np.eye(8, dtype=np.float32))

    def plain(policy):          # no traceable core -> sequential path
        calls[0] += 1
        return float(jnp.mean(policy.matmul("lin_a", x, w)))

    res = explore_heterogeneous(plain, {"lin_a": 10}, lib,
                                multipliers=MULTS[:2], quality_bound=9.9,
                                top_k=2)
    assert res.heterogeneous and calls[0] > 0


# ----------------------------------------------------------------------
# Power model satellites
# ----------------------------------------------------------------------
def test_per_layer_share_zero_total_regression():
    layers = [LayerPower("a", 0, "m1", 0.5), LayerPower("b", 0, "m2", 1.0)]
    # regression: used to raise ZeroDivisionError; mirrors the
    # network_relative_power guard
    assert per_layer_share(layers) == {"a": 0.0, "b": 0.0}
    assert per_layer_share([]) == {}


def test_network_power_for_assignment_partial_coverage():
    counts = {"a": 100, "b": 300}
    got = network_power_for_assignment(counts, {"a": "m"}, {"m": 0.5})
    assert got == pytest.approx((100 * 0.5 + 300 * 1.0) / 400)
    assert network_power_for_assignment({}, {}, {}) == 1.0


# ----------------------------------------------------------------------
# Predict-stage regression pins (DESIGN.md §2.11): the surrogate
# refactor added predictor=/train_fraction= plumbing around stage 1 —
# these pins freeze the exact-predict behavior it must not move.
# ----------------------------------------------------------------------
def test_compose_assignments_min_primary_shortlist_pin():
    """Beam shortlist under a min-direction primary (logit-MAE-style
    components), pinned bit-identically: same order, same rows."""
    c = LayerComponents(
        layers=LAYERS, multipliers=tuple(MULTS),
        quality=np.asarray([[0.001, 0.010, 0.200],
                            [0.001, 0.080, 0.500]]),
        rel_power=np.asarray([1.0, 0.2, 0.02]),
        counts=(100, 300), total_count=400, baseline=0.001,
        direction="min")
    rows = compose_assignments(c, quality_bound=0.05, top_k=6)
    assert [tuple(r.tolist()) for r in rows] == \
        [(1, 1), (0, 1), (1, 0), (0, 0)]


@pytest.fixture(scope="module")
def min_primary_workload():
    """Min-primary (logit_mae) toy workload over the two-matmul net —
    the seed/weights behind the exact-predict pin."""
    from repro.approx.workload import logit_fidelity

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w_a = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    w_b = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)

    def forward(policy, xb):
        y = policy.matmul("lin_a", xb, w_a)
        return policy.matmul("lin_b", jax.nn.relu(y), w_b)

    return logit_fidelity(forward, [x], layer_counts=dict(COUNTS))


def test_explore_heterogeneous_exact_predictor_pin(lib,
                                                   min_primary_workload):
    """predictor="exact" agrees with an in-process sequential
    computation: the baseline, every stage-1 per-layer row, each
    verified shortlist point (accuracy and power, in power order) and
    the selection — and the JSON surface (no surrogate key)."""
    wl = min_primary_workload
    bound = 30.0
    res = explore_heterogeneous(wl, dict(COUNTS), lib, multipliers=MULTS,
                                quality_bound=bound, top_k=6)
    golden = BackendSpec.golden().materialize()

    def measure(overrides):
        policy = ApproxPolicy(default=golden, overrides=[
            (layer, BackendSpec(mode="lut", multiplier=m).materialize(lib))
            for layer, m in overrides])
        return wl.measure(policy)[wl.primary]

    baseline = measure([])
    assert res.baseline_accuracy == baseline
    # per-layer stage-1 rows are the exact sweep, one layer at a time
    assert sorted((p.multiplier, p.layer) for p in res.per_layer) == \
        sorted((m, layer) for m in MULTS for layer in LAYERS)
    for p in res.per_layer:
        assert p.accuracy == measure([(p.layer, p.multiplier)])
    # the verified shortlist: top_k compositions in power order, each
    # measured exactly with count-weighted power
    rel_power = {m: lib.entry(m).rel_power for m in MULTS}
    assert len(res.heterogeneous) == 6
    assert len({p.assignment for p in res.heterogeneous}) == 6
    for p in res.heterogeneous:
        assert p.accuracy == measure(p.assignment)
        assert p.network_rel_power == network_power_for_assignment(
            COUNTS, dict(p.assignment), rel_power)
    powers = [p.network_rel_power for p in res.heterogeneous]
    assert powers == sorted(powers)
    # selection: the lowest-power verified point within the bound (the
    # primary, logit MAE, is minimized)
    feasible = [p for p in res.heterogeneous
                if p.accuracy - baseline <= bound]
    assert res.selected is not None
    assert res.selected.assignment == min(
        feasible, key=lambda p: p.network_rel_power).assignment
    # JSON surface unchanged: no surrogate key on the exact path, and
    # a faithful round-trip
    d = res.to_json_dict()
    assert sorted(d.keys()) == [
        "all_layers", "baseline_accuracy", "baseline_metrics",
        "heterogeneous", "objective_directions", "objectives",
        "per_layer", "primary", "selected"]
    from repro.approx.dse import ExploreResult
    assert ExploreResult.from_json_dict(d).to_json_dict() == d
