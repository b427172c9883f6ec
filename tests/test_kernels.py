"""Per-kernel allclose vs the ref.py pure-jnp oracles, with hypothesis
shape sweeps (interpret=True executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.approx.registry import get_datapath
from repro.core import families, seeds
from repro.core.luts import decompose_lut, exact_mul_lut, lut_from_netlist
from repro.core.netlist import exhaustive_inputs, random_input_planes
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _codes(m, k, n):
    qa = jnp.asarray(RNG.integers(0, 256, (m, k)), jnp.int32)
    qw = jnp.asarray(RNG.integers(0, 256, (k, n)), jnp.int32)
    return qa, qw


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 140), st.integers(1, 150), st.integers(1, 140))
def test_lut_kernel_matches_ref(m, k, n):
    qa, qw = _codes(m, k, n)
    lut = jnp.asarray(exact_mul_lut(8) + 5)   # LUT[0,0] != 0: pad check
    got = ops.approx_matmul_lut(qa, qw, lut)
    want = ref.approx_matmul_lut_ref(qa, qw, lut)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mult", ["bam", "trunc"])
def test_lut_kernel_real_multipliers(mult):
    nl = (families.bam_multiplier(8, 1, 4) if mult == "bam"
          else families.truncated_multiplier(8, 2))
    lut = jnp.asarray(lut_from_netlist(nl, 8))
    qa, qw = _codes(64, 96, 32)
    got = ops.approx_matmul_lut(qa, qw, lut)
    want = ref.approx_matmul_lut_ref(qa, qw, lut)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=4, deadline=None)
@given(st.integers(1, 140), st.integers(1, 150), st.integers(1, 140),
       st.integers(1, 4), st.booleans())
def test_lut_bank_kernel_matches_ref(m, k, n, n_mult, banked_qa):
    qa, qw = _codes(m, k, n)
    if banked_qa:
        qa = jnp.asarray(RNG.integers(0, 256, (n_mult, m, k)), jnp.int32)
    luts = jnp.asarray(RNG.integers(0, 255 * 255, (n_mult, 256, 256)),
                       jnp.int32)
    got = ops.approx_matmul_lut_bank(qa, qw, luts)
    want = ref.approx_matmul_lut_bank_ref(qa, qw, luts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_bank_lane_matches_single_lut_kernel():
    """Equivalence contract: bank lane b == single-LUT kernel with
    luts[b] (what the batched resilience engine relies on)."""
    qa, qw = _codes(70, 130, 50)
    luts = jnp.asarray(RNG.integers(0, 255 * 255, (3, 256, 256)),
                       jnp.int32)
    bank = np.asarray(ops.approx_matmul_lut_bank(qa, qw, luts))
    for b in range(3):
        single = np.asarray(ops.approx_matmul_lut(qa, qw, luts[b]))
        np.testing.assert_array_equal(bank[b], single)


def test_lut_kernel_vmap_dispatches_to_bank():
    """vmap over the LUT axis must reroute to the banked kernel (one
    launch), not batch the single-LUT kernel lane by lane."""
    import jax

    qa, qw = _codes(40, 64, 24)
    luts = jnp.asarray(RNG.integers(0, 255 * 255, (4, 256, 256)),
                       jnp.int32)
    got = jax.vmap(lambda lut: ops.approx_matmul_lut(qa, qw, lut))(luts)
    want = ref.approx_matmul_lut_bank_ref(qa, qw, luts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_kernel_vmap_batched_weights():
    """Batched weights (experts vmapping backend_matmul, NOT a LUT
    bank) stay correct through the custom batching rule."""
    import jax

    qa = jnp.asarray(RNG.integers(0, 256, (3, 20, 40)), jnp.int32)
    qw = jnp.asarray(RNG.integers(0, 256, (3, 40, 24)), jnp.int32)
    lut = jnp.asarray(RNG.integers(0, 255 * 255, (256, 256)), jnp.int32)
    got = jax.vmap(lambda a, w: ops.approx_matmul_lut(a, w, lut))(qa, qw)
    want = np.stack([np.asarray(ref.approx_matmul_lut_ref(qa[i], qw[i],
                                                          lut))
                     for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), want)


def _lowrank(qa, qw, u, v):
    """The low-rank matmul as ``variant="pallas"`` runs it: the XLA
    ``lowrank`` datapath (there is no low-rank Pallas kernel)."""
    return get_datapath("lowrank_pallas").forward_q(qa, qw,
                                                    {"u": u, "v": v})


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 130), st.integers(1, 140), st.integers(1, 130),
       st.integers(1, 6))
def test_lowrank_kernel_matches_ref(m, k, n, r):
    qa, qw = _codes(m, k, n)
    u = jnp.asarray(RNG.normal(size=(r, 256)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(r, 256)).astype(np.float32))
    got = _lowrank(qa, qw, u, v)
    want = ref.lowrank_matmul_ref(qa, qw, u, v)
    # f32 reduction-order noise grows with K (einsum vs flat accumulate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-2)


def test_lowrank_kernel_emulates_exact_multiplier():
    """rank-1 factorization of the exact LUT == exact integer matmul."""
    lut = exact_mul_lut(8)
    fac = decompose_lut(lut, 1)
    qa, qw = _codes(32, 64, 16)
    got = _lowrank(qa, qw, jnp.asarray(fac.u), jnp.asarray(fac.v))
    want = ref.approx_matmul_lut_ref(qa, qw, jnp.asarray(lut)
                                     ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2.0)


@pytest.mark.parametrize("builder,args", [
    (seeds.array_multiplier, (8,)),
    (seeds.ripple_carry_adder, (8,)),
    (families.bam_multiplier, (8, 1, 3)),
    (families.loa_adder, (8, 3)),
])
def test_bitsim_kernel_exhaustive(builder, args):
    nl = builder(*args)
    planes = exhaustive_inputs(nl.n_i)
    got = ops.bitsim(nl, planes)
    want = nl.eval_words(planes)
    assert np.array_equal(got, want)


def test_bitsim_kernel_wide_random():
    nl = seeds.ripple_carry_adder(32)
    planes = random_input_planes(64, 4096, np.random.default_rng(3))
    got = ops.bitsim(nl, planes)
    want = nl.eval_words(planes)
    assert np.array_equal(got, want)


def test_bitsim_ref_oracle_agrees():
    nl = families.bam_multiplier(8, 0, 4).compact()
    planes = exhaustive_inputs(16)
    lo = (planes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (planes >> np.uint64(32)).astype(np.uint32)
    planes32 = np.empty((planes.shape[0], 2 * planes.shape[1]),
                        dtype=np.uint32)
    planes32[:, 0::2] = lo
    planes32[:, 1::2] = hi
    got = ref.bitsim_ref(nl.funcs, nl.in0, nl.in1, nl.outputs,
                         jnp.asarray(planes32))
    want_words = nl.eval_words(planes)
    want32 = np.empty_like(planes32[: nl.n_o])
    want32[:, 0::2] = (want_words & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    want32[:, 1::2] = (want_words >> np.uint64(32)).astype(np.uint32)
    assert np.array_equal(np.asarray(got), want32)
