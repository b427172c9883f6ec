"""Mosaic compile checks: every Pallas kernel that runs on a TPU compiles
for a described (not attached) TPU v5e chip at a ResNet-8 conv shape.

Nothing runs here.  These compiles catch what interpret mode cannot:
lowering refusals, tiling violations, VMEM overruns.  The topology is
described inside a module fixture, never at import, so that under
pytest-xdist only the worker given this file loads the TPU compiler.
The kernels without a Mosaic lowering must refuse to compile for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.approx_matmul import (approx_matmul_lut_bank_pallas,
                                         approx_matmul_lut_pallas)
from repro.kernels.bitsim import bitsim_pallas, bitsim_pop_pallas
from repro.kernels.composed_matmul import (composed_matmul_bank_pallas,
                                           composed_matmul_pallas)
from repro.kernels.fused_matmul import (fused_composed_matmul_bank_pallas,
                                        fused_composed_matmul_pallas,
                                        fused_matmul_bank_pallas,
                                        fused_matmul_pallas)

#: s1_b0_conv2 of ResNet-8 at eval batch 64, as an im2col matmul
M, K, N = 16384, 288, 32
N_MULT = 4
#: an exhaustive 8x8-multiplier simulation: 16 inputs, 2^16 vectors in
#: 2048 uint32 words, a 600-gate netlist, 16 outputs, 8 candidates
N_NODES, N_IO, WORDS, POP = 600, 16, 2048, 8
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep the persistent cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scalars(n=None):
    shape = () if n is None else (n,)
    return [(shape, d) for d in
            (jnp.float32, jnp.int32, jnp.float32, jnp.int32, jnp.float32)]


KERNELS = {
    "approx_matmul_lut_pallas": (
        approx_matmul_lut_pallas.lower,
        lambda: [((M, K), jnp.int32), ((K, N), jnp.int32),
                 ((256, 256), jnp.int32)]),
    "approx_matmul_lut_bank_pallas[shared]": (
        approx_matmul_lut_bank_pallas.lower,
        lambda: [((M, K), jnp.int32), ((K, N), jnp.int32),
                 ((N_MULT, 256, 256), jnp.int32)]),
    "approx_matmul_lut_bank_pallas[banked]": (
        approx_matmul_lut_bank_pallas.lower,
        lambda: [((N_MULT, M, K), jnp.int32), ((K, N), jnp.int32),
                 ((N_MULT, 256, 256), jnp.int32)]),
    "fused_matmul_pallas": (
        fused_matmul_pallas.lower,
        lambda: [((M, K), jnp.float32), ((K, N), jnp.float32),
                 ((256, 256), jnp.int32)] + _scalars()),
    "fused_matmul_bank_pallas": (
        fused_matmul_bank_pallas.lower,
        lambda: [((N_MULT, M, K), jnp.float32), ((K, N), jnp.float32),
                 ((N_MULT, 256, 256), jnp.int32)] + _scalars(N_MULT)),
    "bitsim_pallas": (
        functools.partial(bitsim_pallas.lower, n_nodes=N_NODES, n_i=N_IO,
                          n_o=N_IO),
        lambda: [((N_NODES,), jnp.int32)] * 3
        + [((N_IO,), jnp.int32), ((N_IO, WORDS), jnp.uint32)]),
    "bitsim_pop_pallas": (
        functools.partial(bitsim_pop_pallas.lower, n_nodes=N_NODES,
                          n_i=N_IO, n_o=N_IO),
        lambda: [((POP, N_NODES), jnp.int32)] * 3
        + [((POP, N_IO), jnp.int32), ((N_IO, WORDS), jnp.uint32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    lower, args = KERNELS[name]
    structs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
               for shape, dtype in args()]
    compiled = lower(*structs, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM


def _composed_args():
    codes = jnp.zeros((8, 16), jnp.int32), jnp.zeros((16, 8), jnp.int32)
    return codes + (jnp.zeros((256, 256), jnp.int32),)


@pytest.mark.parametrize("name,call", [
    ("composed_matmul_pallas",
     lambda a, w, lut: composed_matmul_pallas(
         a, w, lut, jnp.uint32(0xFFFFFF), interpret=False)),
    ("composed_matmul_bank_pallas",
     lambda a, w, lut: composed_matmul_bank_pallas(
         a, w, lut[None], jnp.zeros((1,), jnp.uint32), interpret=False)),
    ("fused_composed_matmul_pallas",
     lambda a, w, lut: fused_composed_matmul_pallas(
         a.astype(jnp.float32), w.astype(jnp.float32), lut,
         jnp.uint32(0), jnp.zeros((2,), jnp.int32), 1.0, 0, 1.0, 0,
         4095.0, interpret=False)),
    ("fused_composed_matmul_bank_pallas",
     lambda a, w, lut: fused_composed_matmul_bank_pallas(
         a.astype(jnp.float32), w.astype(jnp.float32), lut[None],
         jnp.zeros((1,), jnp.uint32), jnp.zeros((1, 2), jnp.int32),
         *(jnp.ones((1,)),) * 5, interpret=False)),
])
def test_kernel_without_mosaic_lowering_refuses(name, call):
    """Kernels Mosaic cannot lower raise, naming themselves, instead of
    compiling for (or silently interpreting on) a TPU."""
    with pytest.raises(NotImplementedError, match=name):
        call(*_composed_args())
