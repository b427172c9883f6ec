"""Pallas TPU kernels: composed wide (12/16-bit) LUT matmul.

Width-generic execution (DESIGN.md §2.6): a W-bit approximate multiply
decomposes into base-256 digits ``a = a0 + 256*a1`` and four 8x8 digit
products gathered from the 256x256 TILE LUT pinned in VMEM, reduced by
a shift/add tree whose nodes are library adder semantics
(exact / LOA / truncated — see ``repro.approx.registry.composed_reduce``
and the gate-level ground truth ``repro.core.families.composed_multiplier``).
Products (< 2^32, held in uint32) split into two 16-bit limbs that
accumulate exactly in int32 over K (``K <= MAX_COMPOSED_K``); callers
recombine ``lo + 65536*hi`` in f32 — exact while limb sums stay under
2^24 (K <= 256 at full range), a deterministic f32 rounding floor
beyond that (identical across ref/pallas/banked paths; see DESIGN.md
§2.6).

VMEM budget per program (128/128/128 tiles, K_CHUNK=8):
  lut(256K) + a(bm*bk*4) + w(bk*bn*4) + 4 digit cubes(bm*KC*bn*4)
  ≈ 0.25 + 0.0625 + 0.0625 + 2.0 MiB ≈ 2.4 MiB
— the 4x cube term is the price of the four digit products; the banked
variant pins exactly ONE tile-LUT slice per program (grid over the
multiplier axis), so VMEM stays flat in ``n_mult`` exactly like the
8-bit bank kernel (``approx_matmul.py``).

The per-lane ``mask`` doubles as selector and truncation: wide lanes
AND the reduced product with the netlist's 2W output bits (``0xFFFFFF``
at W=12 — an over-estimating tile can push the tree past 2^24, and the
gate-level circuit keeps only 2W bits), while ``mask == 0`` marks a
narrow (8-bit) lane whose result is the plain ``pp00`` tile sum —
bit-identical to the historical single-LUT kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.approx.registry import composed_reduce

from .platform import no_mosaic

BM, BN, BK = 128, 128, 128
#: k-slice of each gathered (BM, K_CHUNK, BN) digit-product cube
K_CHUNK = 8


def _digit_cubes(a, w, lut, c):
    """Four (mb, K_CHUNK, bn) digit-product cubes for k-chunk ``c``."""
    a_c = jax.lax.dynamic_slice(a, (0, c * K_CHUNK),
                                (a.shape[0], K_CHUNK))
    w_c = jax.lax.dynamic_slice(w, (c * K_CHUNK, 0),
                                (K_CHUNK, w.shape[1]))
    a0, a1 = a_c & 255, a_c >> 8
    w0, w1 = w_c & 255, w_c >> 8

    def pp(x, y):
        idx = x[:, :, None] * 256 + y[None, :, :]
        return jnp.take(lut, idx, axis=0)

    return pp(a0, w0), pp(a0, w1), pp(a1, w0), pp(a1, w1)


def _make_kernel(reduce: tuple, banked: bool):
    def kernel(a_ref, w_ref, lut_ref, mask_ref, lo_ref, hi_ref):
        k_step = pl.program_id(3 if banked else 2)

        @pl.when(k_step == 0)
        def _init():
            lo_ref[...] = jnp.zeros_like(lo_ref)
            hi_ref[...] = jnp.zeros_like(hi_ref)

        a = a_ref[...].reshape(-1, a_ref.shape[-1])  # (BM,BK) W-bit codes
        w = w_ref[...]                               # (BK,BN)
        lut = lut_ref[...].reshape(-1)               # (65536,) tile LUT
        mask = mask_ref[0]                           # 2W-bit product mask
        wide = mask != 0

        def body(c, accs):
            acc_lo, acc_hi = accs
            pp00, pp01, pp10, pp11 = _digit_cubes(a, w, lut, c)
            p = composed_reduce(pp00.astype(jnp.uint32),
                                pp01.astype(jnp.uint32),
                                pp10.astype(jnp.uint32),
                                pp11.astype(jnp.uint32), reduce) & mask
            lo = jnp.where(wide, (p & jnp.uint32(0xFFFF)
                                  ).astype(jnp.int32), pp00)
            hi = jnp.where(wide, (p >> 16).astype(jnp.int32), 0)
            return (acc_lo + jnp.sum(lo, axis=1, dtype=jnp.int32),
                    acc_hi + jnp.sum(hi, axis=1, dtype=jnp.int32))

        nk = a.shape[1] // K_CHUNK
        zeros = jnp.zeros((a.shape[0], w.shape[1]), jnp.int32)
        acc_lo, acc_hi = jax.lax.fori_loop(0, nk, body, (zeros, zeros))
        if banked:
            lo_ref[...] += acc_lo[None]
            hi_ref[...] += acc_hi[None]
        else:
            lo_ref[...] += acc_lo
            hi_ref[...] += acc_hi

    return kernel


def _pad_limbs(flat, mask, reduce, pk):
    """Per-bank limb contribution of ONE K-pad row (codes 0): the
    (masked) composed product at (0,0) for wide lanes, the raw tile
    LUT[0,0] for narrow lanes.  flat: (..., 65536); returns (lo, hi)
    broadcast against the output."""
    t00 = flat[..., 0]
    mask = jnp.asarray(mask, jnp.uint32)
    p00 = composed_reduce(*(4 * (t00.astype(jnp.uint32),)),
                          reduce) & mask
    wide = mask != 0
    lo = jnp.where(wide, (p00 & jnp.uint32(0xFFFF)).astype(jnp.int32),
                   t00)
    hi = jnp.where(wide, (p00 >> 16).astype(jnp.int32), 0)
    return jnp.int32(pk) * lo, jnp.int32(pk) * hi


@functools.partial(jax.jit, static_argnames=("reduce", "interpret"))
def composed_matmul_pallas(qa: jax.Array, qw: jax.Array, lut: jax.Array,
                           mask: jax.Array, reduce: tuple = ("exact", 0),
                           interpret: bool = False) -> jax.Array:
    """qa: (M,K) int32 W-bit codes; qw: (K,N) int32; lut: (256,256)
    int32 tile LUT; mask: scalar uint32 2W-bit product mask (0 selects
    the narrow 8-bit path).  Returns (M,N) f32 ``lo + 65536*hi`` with
    exact int32 limb accumulation."""
    no_mosaic("composed_matmul_pallas", interpret)
    m, k = qa.shape
    k2, n = qw.shape
    assert k == k2
    pm, pn, pk = (-m) % BM, (-n) % BN, (-k) % BK
    qa_p = jnp.pad(qa, ((0, pm), (0, pk)))
    qw_p = jnp.pad(qw, ((0, pk), (0, pn)))
    flat = lut.reshape(-1)
    mask_arr = jnp.asarray(mask, jnp.uint32).reshape(1)
    grid = (qa_p.shape[0] // BM, qw_p.shape[1] // BN, qa_p.shape[1] // BK)
    shape = jax.ShapeDtypeStruct((qa_p.shape[0], qw_p.shape[1]),
                                 jnp.int32)
    lo, hi = pl.pallas_call(
        _make_kernel(reduce, banked=False),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, s: (i, s)),
            pl.BlockSpec((BK, BN), lambda i, j, s: (s, j)),
            pl.BlockSpec((65536,), lambda i, j, s: (0,)),
            pl.BlockSpec((1,), lambda i, j, s: (0,)),
        ],
        out_specs=[pl.BlockSpec((BM, BN), lambda i, j, s: (i, j)),
                   pl.BlockSpec((BM, BN), lambda i, j, s: (i, j))],
        out_shape=[shape, shape],
        interpret=interpret,
    )(qa_p, qw_p, flat, mask_arr)
    lo, hi = lo[:m, :n], hi[:m, :n]
    if pk:
        dlo, dhi = _pad_limbs(flat, mask_arr[0], reduce, pk)
        lo, hi = lo - dlo, hi - dhi
    return lo.astype(jnp.float32) + 65536.0 * hi.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("reduce", "interpret"))
def composed_matmul_bank_pallas(qa: jax.Array, qw: jax.Array,
                                luts: jax.Array, mask: jax.Array,
                                reduce: tuple = ("exact", 0),
                                interpret: bool = False) -> jax.Array:
    """Banked composed matmul: one launch for a whole mixed-width bank.

    qa: (M,K) shared or (n,M,K) banked codes; qw: (K,N); luts:
    (n,256,256) tile LUTs; mask: (n,) uint32 per-lane 2W-bit product
    mask (0 = narrow lane).  Returns (n,M,N) f32, bit-identical per
    lane to ``composed_matmul_pallas`` — grid (n, M/BM, N/BN, K/BK)
    with one VMEM-pinned tile-LUT slice per program.
    """
    no_mosaic("composed_matmul_bank_pallas", interpret)
    banked_a = qa.ndim == 3
    n_mult = luts.shape[0]
    m, k = qa.shape[-2:]
    k2, n = qw.shape
    assert k == k2
    assert not banked_a or qa.shape[0] == n_mult
    pm, pn, pk = (-m) % BM, (-n) % BN, (-k) % BK
    a_pad = ((0, 0), (0, pm), (0, pk)) if banked_a else ((0, pm), (0, pk))
    qa_p = jnp.pad(qa, a_pad)
    qw_p = jnp.pad(qw, ((0, pk), (0, pn)))
    flat = luts.reshape(n_mult, -1)
    mask = jnp.asarray(mask, jnp.uint32).reshape(n_mult)
    grid = (n_mult, qa_p.shape[-2] // BM, qw_p.shape[1] // BN,
            qa_p.shape[-1] // BK)
    if banked_a:
        a_spec = pl.BlockSpec((1, BM, BK), lambda b, i, j, s: (b, i, s))
    else:
        a_spec = pl.BlockSpec((BM, BK), lambda b, i, j, s: (i, s))
    shape = jax.ShapeDtypeStruct(
        (n_mult, qa_p.shape[-2], qw_p.shape[1]), jnp.int32)
    lo, hi = pl.pallas_call(
        _make_kernel(reduce, banked=True),
        grid=grid,
        in_specs=[
            a_spec,
            pl.BlockSpec((BK, BN), lambda b, i, j, s: (s, j)),
            pl.BlockSpec((1, 65536), lambda b, i, j, s: (b, 0)),
            pl.BlockSpec((1,), lambda b, i, j, s: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, BM, BN), lambda b, i, j, s: (b, i, j)),
            pl.BlockSpec((1, BM, BN), lambda b, i, j, s: (b, i, j))],
        out_shape=[shape, shape],
        interpret=interpret,
    )(qa_p, qw_p, flat, mask)
    lo, hi = lo[:, :m, :n], hi[:, :m, :n]
    if pk:
        dlo, dhi = _pad_limbs(flat, mask, reduce, pk)
        lo = lo - dlo[:, None, None]
        hi = hi - dhi[:, None, None]
    return lo.astype(jnp.float32) + 65536.0 * hi.astype(jnp.float32)


def composed_matmul_ref(qa: jax.Array, qw: jax.Array, lut: jax.Array,
                        mask, reduce: tuple = ("exact", 0)) -> jax.Array:
    """Pure-jnp oracle for the composed kernels (one unblocked pass)."""
    from repro.approx.registry import _composed_gather_block
    flat = jnp.asarray(lut, jnp.int32).reshape(-1)
    return _composed_gather_block(qa, qw, flat, mask, reduce)
