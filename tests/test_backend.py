"""Quantization + matmul backends + approx conv (vs lax.conv oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real hypothesis, or the deterministic fallback conftest.py installs
from hypothesis import given, settings, strategies as st

from repro.approx.backend import MatmulBackend, backend_matmul
from repro.approx.layers import ApproxPolicy, conv2d, conv_mult_count
from repro.approx.quant import calibrate, dequantize, quantize
from repro.core.luts import decompose_lut, exact_mul_lut

RNG = np.random.default_rng(0)


@settings(max_examples=20, deadline=None)
@given(st.floats(-100, 100), st.floats(0.01, 50), st.integers(0, 2 ** 31))
def test_quant_roundtrip_bounded(center, spread, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(center + spread * rng.standard_normal(128),
                    jnp.float32)
    qp = calibrate(x)
    err = jnp.abs(dequantize(quantize(x, qp), qp) - x)
    # round-trip error bounded by one quantization step
    assert float(err.max()) <= float(qp.scale) * 0.5001 + 1e-6


def test_int8_close_to_float():
    x = jnp.asarray(RNG.normal(size=(40, 64)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(64, 32)), jnp.float32)
    y = backend_matmul(x, w, MatmulBackend(mode="int8"))
    rel = float(jnp.abs(y - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.05


def test_lut_exact_equals_int8():
    """LUT emulation with the exact multiplier == the exact int8 path."""
    x = jnp.asarray(RNG.normal(size=(3, 5, 32)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(32, 16)), jnp.float32)
    y_lut = backend_matmul(x, w, MatmulBackend(mode="lut",
                                               lut=exact_mul_lut(8)))
    y_int8 = backend_matmul(x, w, MatmulBackend(mode="int8"))
    np.testing.assert_allclose(np.asarray(y_lut), np.asarray(y_int8),
                               rtol=1e-6, atol=1e-6)


def test_lowrank_rank1_exact():
    fac = decompose_lut(exact_mul_lut(8), 1)
    be = MatmulBackend(mode="lowrank", factors_u=np.asarray(fac.u),
                       factors_v=np.asarray(fac.v))
    x = jnp.asarray(RNG.normal(size=(17, 48)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(48, 9)), jnp.float32)
    y = backend_matmul(x, w, be)
    y8 = backend_matmul(x, w, MatmulBackend(mode="int8"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y8), rtol=1e-4,
                               atol=1e-3)


def test_ste_gradient_matches_exact_vjp():
    x = jnp.asarray(RNG.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(16, 4)), jnp.float32)
    be = MatmulBackend(mode="lut", lut=exact_mul_lut(8))

    g_approx = jax.grad(lambda w_: jnp.sum(backend_matmul(x, w_, be) ** 2))(w)
    assert np.isfinite(np.asarray(g_approx)).all()
    # STE backward uses the *forward output* cotangent with exact matmul
    # vjp: for the exact-multiplier LUT they coincide up to quant noise.
    g_true = jax.grad(lambda w_: jnp.sum((x @ w_) ** 2))(w)
    rel = float(jnp.abs(g_approx - g_true).max() / jnp.abs(g_true).max())
    assert rel < 0.1


def test_policy_override_precedence():
    be_a = MatmulBackend(mode="f32")
    be_b = MatmulBackend(mode="int8")
    pol = ApproxPolicy(default=be_a, overrides=[("layer1*", be_b)])
    assert pol.backend_for("layer1.conv") is be_b
    assert pol.backend_for("layer2.conv") is be_a


@pytest.mark.parametrize("stride,pad", [(1, "SAME"), (2, "SAME"),
                                        (1, "VALID"), (2, "VALID")])
def test_conv2d_matches_lax_conv(stride, pad):
    x = jnp.asarray(RNG.normal(size=(2, 16, 16, 3)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(3, 3, 3, 8)), jnp.float32)
    pol = ApproxPolicy(default=MatmulBackend(mode="f32"))
    got = conv2d(pol, "c", x, w, stride=stride, padding=pad)
    want = jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_conv_mult_count():
    # 32x32x3 -> 16 channels 3x3 SAME stride 1: B*32*32*9*3*16
    assert conv_mult_count((2, 32, 32, 3), (3, 3, 3, 16)) \
        == 2 * 32 * 32 * 9 * 3 * 16
    # SAME with stride on an odd extent is a ceil-div: 33 -> 17
    assert conv_mult_count((1, 33, 33, 3), (3, 3, 3, 16), stride=2) \
        == 17 * 17 * 9 * 3 * 16
    # VALID shrinks by the kernel: 32 - 3 + 1 = 30
    assert conv_mult_count((1, 32, 32, 3), (3, 3, 3, 16),
                           padding="VALID") == 30 * 30 * 9 * 3 * 16
    # VALID with stride: floor((32-3)/2)+1 = 15
    assert conv_mult_count((1, 32, 32, 3), (3, 3, 3, 16), stride=2,
                           padding="VALID") == 15 * 15 * 9 * 3 * 16


@pytest.mark.parametrize("stride,pad,size", [
    (1, "SAME", 16), (2, "SAME", 15), (1, "VALID", 16), (2, "VALID", 15),
])
def test_conv_mult_count_matches_executed_output(stride, pad, size):
    """Power accounting must count the dims conv2d actually produces."""
    x = jnp.asarray(RNG.normal(size=(2, size, size, 3)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(3, 3, 3, 8)), jnp.float32)
    pol = ApproxPolicy(default=MatmulBackend(mode="f32"))
    y = conv2d(pol, "c", x, w, stride=stride, padding=pad)
    _, ho, wo, cout = y.shape
    assert conv_mult_count(x.shape, w.shape, stride, pad) \
        == 2 * ho * wo * 3 * 3 * 3 * cout


def test_prepared_weights_match_lowrank():
    """Offline-packed weight tables (serving path) == on-the-fly lowrank."""
    from repro.approx.backend import prepare_weight, prepare_tree
    fac = decompose_lut(exact_mul_lut(8), 2)
    be = MatmulBackend(mode="lowrank", factors_u=np.asarray(fac.u),
                       factors_v=np.asarray(fac.v), rank=2)
    x = jnp.asarray(RNG.normal(size=(9, 48)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(48, 24)), jnp.float32)
    y_ref = backend_matmul(x, w, be)
    y_prep = backend_matmul(x, prepare_weight(w, be), be)
    scale = float(jnp.abs(y_ref).max())
    assert float(jnp.abs(y_prep - y_ref).max()) < 0.02 * scale + 0.05

    # tree packing: projection leaves become dicts, others untouched
    tree = {"blocks": {"wq": jnp.ones((4, 8, 8)), "norm1": jnp.ones((8,))},
            "embed": jnp.ones((16, 8))}
    packed = prepare_tree(tree, be)
    assert "tabs" in packed["blocks"]["wq"]
    assert packed["blocks"]["wq"]["tabs"].shape == (4, 2, 8, 8)
    assert packed["blocks"]["norm1"].shape == (8,)
    assert packed["embed"].shape == (16, 8)


def test_pallas_backend_matches_jnp_backend():
    lut = exact_mul_lut(8)
    x = jnp.asarray(RNG.normal(size=(9, 40)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(40, 7)), jnp.float32)
    y_jnp = backend_matmul(x, w, MatmulBackend(mode="lut", lut=lut))
    y_pal = backend_matmul(x, w, MatmulBackend(mode="lut", lut=lut,
                                               use_pallas=True))
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)
