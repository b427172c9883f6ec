"""Differential + integration contract for the fused datapath
(DESIGN.md §2.10).

Three layers of gates:

* ops-level — every fused kernel (single-LUT, banked, composed wide,
  composed banked) is BIT-IDENTICAL to its jnp oracle in ``ref.py`` at
  8/12/16-bit, including non-block-multiple shapes and the
  ``custom_vmap`` bank collapse;
* integration — the ``variant="fused"`` spec matches ``variant="ref"``
  through ``backend_matmul``/``bank_eval``/``policy_bank_eval`` under
  jit (the incumbent jitted-sequential comparison idiom from
  ``test_composed.py``), plus the mixed-reduce bank capability that
  exists ONLY on the fused variant;
* trace counts — a banked fused sweep stays O(1) compiled programs in
  the number of lanes, audited both by user-function trace counting and
  by ``compile_cache.trace_audit`` backend-compile deltas.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.backend import backend_matmul
from repro.approx.layers import (ApproxPolicy, bank_eval, policy_bank_eval,
                                 policy_for_lane)
from repro.approx.quant import calibrate, scalar_params
from repro.approx.registry import encode_reduce, product_mask
from repro.approx.specs import BackendSpec, PolicyBank, bank_for
from repro.core.library import build_default_library
from repro.kernels import ops, ref
from repro.launch.compile_cache import trace_audit

N16 = "mul16u_c_mul8u_trunc6_loa4"
N16B = "mul16u_c_mul8u_exact_trunc3"
N12 = "mul12u_c_mul8u_exact_loa4"


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def lut8(rng):
    return jnp.asarray(rng.integers(0, 255 * 255,
                                    (256, 256)).astype(np.int32))


@pytest.fixture(scope="module")
def lib():
    lib = build_default_library("tiny")
    for base, width, red in (("mul8u_trunc6", 16, "loa4"),
                             ("mul8u_exact", 12, "loa4"),
                             ("mul8u_exact", 16, "trunc3")):
        lib.add_composed(base, width, red, samples=512)
    return lib


# ----------------------------------------------------------------------
# ops-level differential suite: fused kernels vs jnp oracles
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 96, 64), (7, 150, 9),
                                   (130, 260, 200)])
def test_fused_matmul_identical(rng, lut8, shape):
    m, k, n = shape
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    sp = scalar_params(calibrate(x), calibrate(w))
    got = ops.fused_matmul_lut(x, w, lut8, *sp)
    want = ref.fused_matmul_ref(x, w, lut8, *sp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _bank_inputs(rng, n_lanes=3, m=9, k=200, n=70):
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    luts = jnp.asarray(rng.integers(0, 255 * 255,
                                    (n_lanes, 256, 256)).astype(np.int32))
    sp = scalar_params(calibrate(x), calibrate(w))
    sp_n = tuple(jnp.broadcast_to(jnp.asarray(v), (n_lanes,)) for v in sp)
    return x, w, luts, sp_n


def test_fused_bank_shared_x_identical(rng):
    x, w, luts, sp_n = _bank_inputs(rng)
    got = ops.fused_matmul_lut_bank(x, w, luts, *sp_n)
    want = ref.fused_matmul_bank_ref(x, w, luts, *sp_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_vmap_collapses_to_bank(rng):
    x, w, luts, sp_n = _bank_inputs(rng)
    got = jax.vmap(ops.fused_matmul_lut,
                   in_axes=(None, None, 0, 0, 0, 0, 0, 0))(x, w, luts,
                                                           *sp_n)
    want = ref.fused_matmul_bank_ref(x, w, luts, *sp_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_bank_batched_x_identical(rng):
    _, w, luts, _ = _bank_inputs(rng)
    xb = jnp.asarray(rng.normal(size=(3, 9, 200)).astype(np.float32))
    per = [scalar_params(calibrate(xb[i]), calibrate(w)) for i in range(3)]
    sp_n = tuple(jnp.stack([jnp.asarray(per[i][j]) for i in range(3)])
                 for j in range(5))
    got = ops.fused_matmul_lut_bank(xb, w, luts, *sp_n)
    want = ref.fused_matmul_bank_ref(xb, w, luts, *sp_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", [12, 16])
@pytest.mark.parametrize("red", [("exact", 0), ("trunc", 4), ("loa", 6)])
def test_fused_composed_identical(rng, lut8, bits, red):
    mask = product_mask(2 * bits)
    rcode = jnp.asarray(encode_reduce(red), jnp.int32)
    x = jnp.asarray(rng.normal(size=(5, 100)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(100, 33)).astype(np.float32))
    sp = scalar_params(calibrate(x, bits=bits), calibrate(w, bits=bits))
    got = ops.fused_composed_matmul_lut(x, w, lut8, mask, rcode, *sp)
    want = ref.fused_composed_matmul_ref(x, w, lut8, mask, *sp, reduce=red)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _composed_bank_inputs(rng):
    """Mixed width AND mixed reduce AND a narrow lane (mask=0)."""
    tiles = jnp.asarray(rng.integers(0, 255 * 255,
                                     (3, 256, 256)).astype(np.int32))
    masks = jnp.asarray([int(product_mask(24)), 0, int(product_mask(32))],
                        dtype=jnp.uint32)
    reduces = [("trunc", 3), ("exact", 0), ("loa", 8)]
    rcodes = jnp.asarray([encode_reduce(r) for r in reduces], jnp.int32)
    x = jnp.asarray(rng.normal(size=(6, 90)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(90, 40)).astype(np.float32))
    sps = [scalar_params(calibrate(x, bits=b), calibrate(w, bits=b))
           for b in (12, 8, 16)]
    sp_n = tuple(jnp.stack([jnp.asarray(sps[i][j]) for i in range(3)])
                 for j in range(5))
    return x, w, tiles, masks, rcodes, reduces, sp_n


def test_fused_composed_bank_mixed_identical(rng):
    x, w, tiles, masks, rcodes, reduces, sp_n = _composed_bank_inputs(rng)
    got = ops.fused_composed_matmul_lut_bank(x, w, tiles, masks, rcodes,
                                             *sp_n)
    want = ref.fused_composed_matmul_bank_ref(x, w, tiles, masks, reduces,
                                              *sp_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_composed_vmap_collapses_to_bank(rng):
    x, w, tiles, masks, rcodes, reduces, sp_n = _composed_bank_inputs(rng)
    got = jax.vmap(ops.fused_composed_matmul_lut,
                   in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0, 0))(
        x, w, tiles, masks, rcodes, *sp_n)
    want = ref.fused_composed_matmul_bank_ref(x, w, tiles, masks, reduces,
                                              *sp_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------
# integration: the fused spec variant through the backend + engines
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mult,bw", [("mul8u_trunc2", None),
                                     (N12, 12), (N16, 16)])
def test_spec_fused_matches_ref_variant(rng, lib, mult, bw):
    x = jnp.asarray(rng.normal(size=(8, 96)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(96, 24)).astype(np.float32))
    outs = {}
    for variant in ("ref", "fused"):
        be = BackendSpec(mode="lut", multiplier=mult, variant=variant,
                         bit_width=bw).materialize(lib)
        fn = jax.jit(lambda a, b, _be=be: backend_matmul(a, b, _be))
        outs[variant] = np.asarray(fn(x, w))
    np.testing.assert_array_equal(outs["ref"], outs["fused"])


def _toy_eval(rng):
    """A two-layer toy eval: its traceable core, a sequential leg and
    the list its core appends to once per trace."""
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w_a = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    w_b = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    traces = []

    def traceable(policy):
        traces.append(1)
        y = policy.matmul("lin_a", x, w_a)
        y = policy.matmul("lin_b", jax.nn.relu(y), w_b)
        return jnp.mean(y)

    def sequential(policy):
        # the incumbent comparison idiom: the sequential leg runs under
        # jit too, so both legs see the same compilation context
        return float(jax.jit(lambda: traceable(policy))())

    return traceable, sequential, traces


@pytest.fixture(scope="module")
def toy_eval(rng):
    return _toy_eval(rng)


MIXED = ["mul8u_exact", "mul8u_trunc6", N16, N12]


def test_bank_eval_fused_bit_identical(lib, toy_eval):
    traceable, sequential, _ = toy_eval
    bank = bank_for(MIXED, lib)
    banked = np.asarray(bank_eval(traceable, bank, variant="fused"))
    seq = np.asarray(
        [sequential(ApproxPolicy(default=BackendSpec.from_library(
            n, variant="fused").materialize(lib))) for n in MIXED],
        dtype=banked.dtype)
    np.testing.assert_array_equal(banked, seq)


def test_mixed_reduce_bank_requires_optin(lib):
    with pytest.raises(ValueError, match="mixed"):
        bank_for([N16, N16B], lib)


def test_mixed_reduce_bank_fused_only(lib, toy_eval):
    traceable, _, _ = toy_eval
    bank = bank_for([N16, N16B, "mul8u_exact"], lib, mixed_reduce=True)
    assert bank.is_mixed_reduce
    with pytest.raises(ValueError, match="fused"):
        bank_eval(traceable, bank, variant="ref")


def test_mixed_reduce_bank_fused_bit_identical(lib, toy_eval):
    traceable, sequential, _ = toy_eval
    names = [N16, N16B, "mul8u_exact"]
    bank = bank_for(names, lib, mixed_reduce=True)
    banked = np.asarray(bank_eval(traceable, bank, variant="fused"))
    seq = np.asarray(
        [sequential(ApproxPolicy(default=BackendSpec.from_library(
            n, variant="fused").materialize(lib))) for n in names],
        dtype=banked.dtype)
    np.testing.assert_array_equal(banked, seq)


def test_policy_bank_fused_bit_identical(lib, toy_eval):
    traceable, sequential, _ = toy_eval
    pbank = PolicyBank.from_assignments(
        [{"lin_a": "mul8u_exact", "lin_b": N16},
         {"lin_a": N12, "lin_b": "mul8u_trunc6"}],
        lib, layers=("lin_a", "lin_b"))
    banked = np.asarray(policy_bank_eval(traceable, pbank, variant="fused"))
    seq = np.asarray(
        [sequential(policy_for_lane(pbank, p,
                                    variant="fused").materialize(lib))
         for p in range(2)], dtype=banked.dtype)
    np.testing.assert_array_equal(banked, seq)


# ----------------------------------------------------------------------
# trace-count gates: banked fused sweeps are O(1) compiled programs
# ----------------------------------------------------------------------
def test_fused_bank_sweep_single_trace(lib):
    # a traceable of its own: the module's shared one already has its
    # banked program, which bank_eval runs again without a trace
    traceable, _, traces = _toy_eval(np.random.default_rng(0))
    bank = bank_for(MIXED, lib)
    bank_eval(traceable, bank, variant="fused")
    assert len(traces) == 1, (
        f"mixed-width fused bank sweep traced the model "
        f"{len(traces)} times; the banked engine must lower ONE program")


def test_fused_bank_sweep_o1_compiles(lib, toy_eval):
    """Backend-compile count must not grow with the number of lanes."""
    traceable, _, _ = toy_eval

    def _compiles(names):
        bank = bank_for(tuple(names), lib)
        jax.clear_caches()
        with trace_audit() as counts:
            bank_eval(traceable, bank, variant="fused")
        return counts.traced_programs

    # both lane counts exercise the wide (mixed-width) banked path
    n2 = _compiles([N16, N12])
    n4 = _compiles(MIXED)
    assert n4 <= n2, (
        f"fused bank sweep compiled {n4} programs for 4 lanes vs "
        f"{n2} for 2 — lane count leaked into compilation")