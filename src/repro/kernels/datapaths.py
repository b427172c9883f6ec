"""Pallas-backed datapath registrations (DESIGN.md §2.1, §4).

Imported lazily by ``repro.approx.registry.get_datapath`` the first time
a ``*_pallas`` datapath is requested, so the approx core never depends
on the kernel layer at import time.  The packs are shared with the
reference datapaths — only ``forward_q`` routes through the Pallas
kernels (interpret-mode on CPU, Mosaic on TPU; see ``ops.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.approx.quant import calibrate, scalar_params
from repro.approx.registry import (MAX_COMPOSED_K, Datapath,
                                   LowRankDatapath, encode_reduce, pack_lut,
                                   register_datapath)

from .ops import (approx_matmul_lut, composed_matmul_lut,
                  fused_composed_matmul_lut, fused_matmul_lut)


@register_datapath("lut_pallas")
class LutPallasDatapath(Datapath):
    """Bit-true LUT emulation through the Pallas texture-gather kernels
    — width-generic (DESIGN.md §2.6): 8-bit specs run the historical
    single-LUT kernel; composed wide specs run the tiled 8x8
    partial-product kernel on the tile LUT.

    Bankable: under the batched engine's vmap, the ops' custom batching
    rules reroute the whole LUT bank to the banked kernels
    (``approx_matmul.py`` / ``composed_matmul.py``, grid over the
    multiplier axis) instead of batching the single-LUT kernel
    lane by lane."""

    # kernel does its own blocking, so block_m is not a spec field
    spec_fields = ("multiplier", "bit_width", "reduce_adder")
    bankable = True

    def pack(self, spec, library) -> dict:
        return pack_lut(spec, library)

    def forward_q(self, qa, qw, consts):
        if consts.get("composed"):
            if qa.shape[-1] > MAX_COMPOSED_K:
                raise ValueError(
                    f"K={qa.shape[-1]} exceeds int32-safe composed "
                    f"limb accumulation bound {MAX_COMPOSED_K}")
            return composed_matmul_lut(qa, qw, jnp.asarray(consts["lut"]),
                                       consts["mask"],
                                       reduce=consts["reduce"])
        return approx_matmul_lut(qa, qw, jnp.asarray(consts["lut"]))


@register_datapath("lut_fused")
class LutFusedDatapath(Datapath):
    """Single-program LUT emulation (DESIGN.md §2.10): the backend hands
    this datapath the FLOAT operands and the whole
    quantize → LUT-gather → int32-accumulate → correct/dequant chain
    runs as ONE ``pallas_call`` (plus the thin f32 epilogue), instead of
    the two-step quantize-then-``forward_q`` pipeline.  Bit-identical to
    ``lut``/``lut_pallas`` at every width by the fused kernels'
    differential contract (``tests/test_fused_matmul.py``).

    Bankable: the fused ops' custom batching rules collapse a vmapped
    LUT axis into the banked fused kernels, and — beyond the static-tree
    banked engines — the composed fused kernel takes the reduction tree
    as RUNTIME data (``reduce_code``), so one compiled program can mix
    reduction families across bank lanes (``LutBank.mixed_reduce``)."""

    spec_fields = ("multiplier", "bit_width", "reduce_adder")
    bankable = True
    fused = True

    def pack(self, spec, library) -> dict:
        return pack_lut(spec, library)

    def forward_fused(self, x2d, w, consts):
        bits = consts.get("bits", 8)
        qp_a = calibrate(x2d, bits=bits)
        qp_w = calibrate(w, bits=bits)
        sp = scalar_params(qp_a, qp_w)
        if consts.get("composed"):
            if x2d.shape[-1] > MAX_COMPOSED_K:
                raise ValueError(
                    f"K={x2d.shape[-1]} exceeds int32-safe composed "
                    f"limb accumulation bound {MAX_COMPOSED_K}")
            rcode = consts.get("reduce_code")
            if rcode is None:
                rcode = jnp.asarray(encode_reduce(consts["reduce"]),
                                    jnp.int32)
            return fused_composed_matmul_lut(
                x2d, w, jnp.asarray(consts["lut"]),
                jnp.asarray(consts["mask"], jnp.uint32), rcode, *sp)
        return fused_matmul_lut(x2d, w, jnp.asarray(consts["lut"]), *sp)

    def forward_q(self, qa, qw, consts):
        raise TypeError(
            "lut_fused is a fused datapath: the backend routes float "
            "operands through forward_fused, never quantized codes")


@register_datapath("lowrank_pallas")
class LowRankPallasDatapath(LowRankDatapath):
    """The rank-R factored emulation has no Pallas kernel of its own:
    Mosaic lowers no in-kernel table lookup, and with the lookups in
    XLA what is left is R plain matmuls, which is the XLA ``lowrank``
    datapath.  ``variant="pallas"`` therefore runs that datapath."""
