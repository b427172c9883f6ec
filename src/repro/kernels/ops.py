"""jit'd public wrappers for the Pallas kernels.

On the CPU the kernels execute in ``interpret=True`` mode — the kernel
body runs verbatim, which is how they are validated against the
``ref.py`` oracles.  On a TPU the same calls lower to Mosaic
(``platform.interpret``); kernels without a Mosaic lowering raise there.
"""
from __future__ import annotations

import functools

import jax
import jax.custom_batching
import jax.numpy as jnp
import numpy as np

from .approx_matmul import (approx_matmul_lut_bank_pallas,
                            approx_matmul_lut_pallas)
from .composed_matmul import (composed_matmul_bank_pallas,
                              composed_matmul_pallas)
from .fused_matmul import (fused_composed_matmul_bank_pallas,
                           fused_composed_matmul_pallas,
                           fused_matmul_bank_pallas, fused_matmul_pallas)
from .bitsim import bitsim_pallas, bitsim_pop_pallas
from . import platform


@jax.custom_batching.custom_vmap
def approx_matmul_lut(qa: jax.Array, qw: jax.Array, lut: jax.Array
                      ) -> jax.Array:
    """Bit-true approximate matmul on uint8 codes. (M,K)x(K,N)->(M,N) i32.

    ``vmap`` over the LUT argument does NOT fall back to rank-by-rank
    batching: a custom batching rule reroutes the whole batch to the
    banked kernel (grid over the multiplier axis), which is how the
    batched resilience engine turns an n-multiplier sweep into one
    launch (DESIGN.md §2.4).
    """
    return approx_matmul_lut_pallas(qa, qw, lut,
                                    interpret=platform.interpret())


@approx_matmul_lut.def_vmap
def _approx_matmul_lut_vmap(axis_size, in_batched, qa, qw, lut):
    qa_b, qw_b, lut_b = in_batched
    if qw_b:
        # batched weights (e.g. experts vmapping backend_matmul) are not
        # a LUT bank: keep pallas_call's native parallel batching rule.
        out = jax.vmap(
            lambda a, w, l: approx_matmul_lut_pallas(
                a, w, l, interpret=platform.interpret()),
            in_axes=(0 if qa_b else None, 0, 0 if lut_b else None),
        )(qa, qw, lut)
        return out, True
    luts = lut if lut_b else jnp.broadcast_to(lut, (axis_size,) + lut.shape)
    out = approx_matmul_lut_bank(qa, qw, luts)
    return out, True


def approx_matmul_lut_bank(qa: jax.Array, qw: jax.Array, luts: jax.Array
                           ) -> jax.Array:
    """Banked bit-true matmul: one launch for a whole LUT bank.
    qa: (M,K) shared or (n,M,K) banked codes; luts: (n,256,256)
    -> (n,M,N) i32, bit-identical per bank to ``approx_matmul_lut``."""
    return approx_matmul_lut_bank_pallas(qa, qw, luts,
                                         interpret=platform.interpret())


@functools.lru_cache(maxsize=None)
def _composed_op(reduce: tuple):
    """The composed (wide-width) LUT matmul op for one static reduce
    tree, with the same bank-collapsing batching rule as
    ``approx_matmul_lut``: vmap over (lut, wide) routes the whole
    mixed-width bank to the banked composed kernel — one launch, grid
    over the multiplier axis (DESIGN.md §2.6) — instead of batching
    the single-tile kernel lane by lane."""

    @jax.custom_batching.custom_vmap
    def op(qa, qw, lut, mask):
        return composed_matmul_pallas(qa, qw, lut, mask, reduce=reduce,
                                      interpret=platform.interpret())

    @op.def_vmap
    def _op_vmap(axis_size, in_batched, qa, qw, lut, mask):
        qa_b, qw_b, lut_b, mask_b = in_batched
        if qw_b:
            # batched weights (experts) are not a LUT bank: native rule
            out = jax.vmap(
                lambda a, w, l, mk: composed_matmul_pallas(
                    a, w, l, mk, reduce=reduce,
                    interpret=platform.interpret()),
                in_axes=(0 if qa_b else None, 0, 0 if lut_b else None,
                         0 if mask_b else None),
            )(qa, qw, lut, mask)
            return out, True
        luts = (lut if lut_b
                else jnp.broadcast_to(lut, (axis_size,) + lut.shape))
        masks = (mask if mask_b
                 else jnp.broadcast_to(jnp.asarray(mask), (axis_size,)))
        out = composed_matmul_bank_pallas(qa, qw, luts, masks,
                                          reduce=reduce,
                                          interpret=platform.interpret())
        return out, True

    return op


def composed_matmul_lut(qa: jax.Array, qw: jax.Array, lut: jax.Array,
                        mask, reduce: tuple = ("exact", 0)) -> jax.Array:
    """Composed wide approximate matmul on W-bit codes through the
    256x256 tile LUT.  (M,K)x(K,N)->(M,N) f32 (exact int32 limb
    accumulation recombined as ``lo + 65536*hi``).  ``mask`` is the
    per-call (or per vmapped lane) 2W-bit product mask — the composed
    product is truncated to the gate netlist's output width, and
    ``mask == 0`` selects the plain 8-bit tile sum instead."""
    return _composed_op(tuple(reduce))(
        qa, qw, lut, jnp.asarray(mask, jnp.uint32))


def _bcast(v, batched: bool, axis_size: int):
    v = jnp.asarray(v)
    return v if batched else jnp.broadcast_to(v, (axis_size,) + v.shape)


@jax.custom_batching.custom_vmap
def fused_matmul_lut(x: jax.Array, w: jax.Array, lut: jax.Array,
                     sa, za, sw, zw, qmax) -> jax.Array:
    """Fused 8-bit approximate matmul on FLOAT operands: in-kernel
    quantize (pre-calibrated scalars from ``quant.scalar_params``),
    LUT gather, int32 accumulation, f32 correction + dequant — one
    Pallas program, bit-identical to the two-step pipeline
    (DESIGN.md §2.10).  (M,K)x(K,N) -> (M,N) f32.

    Like ``approx_matmul_lut``, a custom batching rule reroutes a vmap
    over (lut, scalars) to the banked fused kernel so bank sweeps stay
    one launch; batched weights keep the native rule."""
    return fused_matmul_pallas(x, w, lut, sa, za, sw, zw, qmax,
                               interpret=platform.interpret())


@fused_matmul_lut.def_vmap
def _fused_matmul_lut_vmap(axis_size, in_batched, x, w, lut,
                           sa, za, sw, zw, qmax):
    x_b, w_b, lut_b = in_batched[:3]
    if w_b:
        # batched weights (experts) are not a LUT bank: native rule
        out = jax.vmap(
            lambda *a: fused_matmul_pallas(*a, interpret=platform.interpret()),
            in_axes=tuple(0 if b else None for b in in_batched),
        )(x, w, lut, sa, za, sw, zw, qmax)
        return out, True
    luts = _bcast(lut, lut_b, axis_size)
    scalars = [_bcast(v, b, axis_size)
               for v, b in zip((sa, za, sw, zw, qmax), in_batched[3:])]
    # x stays SHARED (M,K) when unbatched — the banked kernel grids over
    # the lane axis and re-quantizes the shared tile per lane.
    out = fused_matmul_lut_bank(x, w, luts, *scalars)
    return out, True


def fused_matmul_lut_bank(x: jax.Array, w: jax.Array, luts: jax.Array,
                          sa, za, sw, zw, qmax) -> jax.Array:
    """Banked fused matmul: one launch per LUT bank, per-lane quant
    scalars (n,).  x: (M,K) shared or (n,M,K) banked floats;
    luts: (n,256,256) -> (n,M,N) f32, per lane bit-identical to
    ``fused_matmul_lut``.  LUT slices are DMA double-buffered."""
    return fused_matmul_bank_pallas(x, w, luts, sa, za, sw, zw, qmax,
                                    interpret=platform.interpret())


@jax.custom_batching.custom_vmap
def fused_composed_matmul_lut(x: jax.Array, w: jax.Array,
                              lut: jax.Array, mask, rcode,
                              sa, za, sw, zw, qmax) -> jax.Array:
    """Fused composed wide (12/16-bit) approximate matmul on floats.
    ``mask`` is the 2W-bit product mask (0 = narrow lane) and ``rcode``
    the ``registry.encode_reduce`` (kind, k) int32 pair — the reduce
    tree is RUNTIME data here, so every adder family (and any mix of
    them across vmapped lanes) shares one compiled program, unlike the
    per-reduce ``composed_matmul_lut`` specializations."""
    return fused_composed_matmul_pallas(x, w, lut, mask, rcode,
                                        sa, za, sw, zw, qmax,
                                        interpret=platform.interpret())


@fused_composed_matmul_lut.def_vmap
def _fused_composed_matmul_lut_vmap(axis_size, in_batched, x, w, lut,
                                    mask, rcode, sa, za, sw, zw, qmax):
    x_b, w_b, lut_b = in_batched[:3]
    if w_b:
        out = jax.vmap(
            lambda *a: fused_composed_matmul_pallas(
                *a, interpret=platform.interpret()),
            in_axes=tuple(0 if b else None for b in in_batched),
        )(x, w, lut, mask, rcode, sa, za, sw, zw, qmax)
        return out, True
    luts = _bcast(lut, lut_b, axis_size)
    rest = [_bcast(v, b, axis_size)
            for v, b in zip((mask, rcode, sa, za, sw, zw, qmax),
                            in_batched[3:])]
    out = fused_composed_matmul_lut_bank(x, w, luts, *rest)
    return out, True


def fused_composed_matmul_lut_bank(x: jax.Array, w: jax.Array,
                                   luts: jax.Array, masks, rcodes,
                                   sa, za, sw, zw, qmax) -> jax.Array:
    """Banked composed fused matmul: per-lane masks (n,), reduce codes
    (n,2) and quant scalars (n,) in ONE program — mixed-width AND
    mixed-reduce banks evaluate in a single launch."""
    return fused_composed_matmul_bank_pallas(
        x, w, luts, masks, rcodes, sa, za, sw, zw, qmax,
        interpret=platform.interpret())


def bitsim(netlist, planes64: np.ndarray) -> np.ndarray:
    """Evaluate a ``repro.core.netlist.Netlist`` on uint64 bit-planes via
    the Pallas simulator (planes are split to uint32 lanes and rejoined).
    Drop-in equivalent of ``netlist.eval_words``."""
    out32 = np.asarray(bitsim_pallas(
        jnp.asarray(netlist.funcs), jnp.asarray(netlist.in0),
        jnp.asarray(netlist.in1), jnp.asarray(netlist.outputs),
        jnp.asarray(split_planes64(planes64)),
        n_nodes=netlist.n_nodes, n_i=netlist.n_i, n_o=netlist.n_o,
        interpret=platform.interpret(),
    ))
    return join_planes32(out32)


def split_planes64(planes64: np.ndarray) -> np.ndarray:
    """(n, W) uint64 bit-planes -> (n, 2W) uint32 lanes, low word first
    (the lane layout both bitsim kernels consume)."""
    n, w64 = planes64.shape
    lo = (planes64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (planes64 >> np.uint64(32)).astype(np.uint32)
    planes32 = np.empty((n, 2 * w64), dtype=np.uint32)
    planes32[:, 0::2] = lo
    planes32[:, 1::2] = hi
    return planes32


def join_planes32(planes32: np.ndarray) -> np.ndarray:
    """Inverse of ``split_planes64`` on the trailing axis (any rank)."""
    return (planes32[..., 0::2].astype(np.uint64)
            | (planes32[..., 1::2].astype(np.uint64) << np.uint64(32)))


def bitsim_pop(netlists, planes64: np.ndarray) -> np.ndarray:
    """Evaluate a population of same-interface netlists on shared
    uint64 bit-planes in ONE Pallas program (DESIGN.md §2.9).

    Returns (P, n_o, W) uint64 — row p bit-identical to
    ``netlists[p].eval_words(planes64)``.  Mixed node counts are padded
    with inactive const0 nodes (``stack_netlists``).
    """
    from repro.core.netlist import stack_netlists
    funcs, in0, in1, outs = stack_netlists(list(netlists))
    first = netlists[0]
    out32 = np.asarray(bitsim_pop_pallas(
        jnp.asarray(funcs), jnp.asarray(in0), jnp.asarray(in1),
        jnp.asarray(outs), jnp.asarray(split_planes64(planes64)),
        n_nodes=funcs.shape[1], n_i=first.n_i, n_o=first.n_o,
        interpret=platform.interpret(),
    ))
    return join_planes32(out32)
