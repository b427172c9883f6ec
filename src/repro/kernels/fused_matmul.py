"""Pallas TPU kernels: FUSED quantize→LUT→accumulate datapath.

One ``pallas_call`` runs the integer half of the approximate-matmul
datapath (DESIGN.md §2.10): float activation tiles stream in, are
affine quantized in-register with pre-calibrated scalar params (SMEM),
and are contracted against the LUT exactly in int32.  Versus the
two-step path (quantize → ``approx_matmul_lut`` → correct/dequant in
XLA) no int32 activation-code tensor is materialized in HBM — only the
(M,N) accumulator leaves the program.

8-bit kernels use the one-hot MXU contraction of ``approx_matmul.py``.
The weights are small next to the activations, so their codes are
quantized in the caller (same op order as ``quant.quantize``) and
resolve the LUT columns into the byte-plane tables; one extra table
column holds the code value itself, so the same contraction also
returns the activation row sums ``Σ_k qa`` the zero-point correction
needs.  K-padding rows of the tables are zero, which masks padded
activation codes out of both.

The f32 zero-point correction + dequant deliberately stays in the
jitted CALLER, written with the same expression shapes as
``repro.approx.backend._quantized_matmul``: XLA contracts adjacent
same-shape ``mul``+``add`` pairs into single-rounding FMAs, and whether
it does so depends on the surrounding computation — an in-kernel f32
epilogue rounds differently from the two-step pipeline at wide widths
(zero-point products past 2^24), while the caller-side epilogue
compiles to the same broadcast-protected HLO structure as the
reference and stays bit-identical.  Everything UP TO the correction is
integer arithmetic and therefore exact in any compilation context.

The composed wide (12/16-bit) kernels still gather digit products from
the VMEM-resident 256x256 tile LUT, which Mosaic does not lower: they
run in interpret mode on the CPU only and raise on a TPU (ROADMAP.md
S1).  Their banked variants double-buffer the LUT through VMEM scratch
(the bank's LUT stack stays in HBM, ``memory_space=ANY``, and each
bank's first tile prefetches the next bank's 256 KiB slice), with row
blocking ``bm = min(128, ceil8(M))``:

  composed banked: 2*lut(512K) + tiles(128K) + 4 cubes(1.0M)
                   + 2 acc limbs(132K) + outs(128K)          ≈ 1.9 MiB

(K_CHUNK 4 fits the 4 digit cubes next to the second LUT slot;
chunking is int-associative so it cannot change results).  They take
the reduction tree as RUNTIME data — an ``encode_reduce`` ``(kind, k)``
int pair in SMEM, applied via ``composed_reduce_dyn`` — so one compiled
program serves every adder family and mixed-reduce banks collapse to a
single trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.approx.registry import (MAX_COMPOSED_K, MAX_LUT_K,
                                   composed_reduce_dyn)

from .approx_matmul import lut_contract, lut_tables, quant_codes
from .composed_matmul import BK, BM, BN
from .platform import no_mosaic

#: K-chunk of the composed fused kernels: 4 digit cubes per chunk must
#: coexist with the second LUT scratch slot (module docstring budget).
CK_CHUNK = 4


def _row_block(m: int) -> int:
    """Shape-adaptive row block: full 128 rows for large M, the 8-row
    f32 tile floor for decode-like shapes (no 128-row gather padding)."""
    return max(8, min(BM, ((m + 7) // 8) * 8))


def _k_masked(qa, qw, k_step, k, pk):
    """Zero the codes of K-padding columns (static no-op when pk == 0)
    so pad products hit LUT[0, 0] — subtracted exactly in the integer
    epilogue — and contribute nothing to the zero-point row/col sums."""
    if not pk:
        return qa, qw
    base = k_step * BK
    ia = base + jax.lax.broadcasted_iota(jnp.int32, (1, BK), 1)
    iw = base + jax.lax.broadcasted_iota(jnp.int32, (BK, 1), 0)
    return jnp.where(ia < k, qa, 0), jnp.where(iw < k, qw, 0)


def _dequant(s, row, col, za, zw, sa, sw, k: int):
    """Caller-side f32 correction + dequant: the exact expression of
    ``backend._quantized_matmul``'s non-exact branch.  s: (M,N) f32;
    row: (M,) i32; col: (N,) i32.

    Each correction product passes through ``jnp.trunc`` before the
    subtract chain.  In interpret mode the pallas program is INLINE
    HLO, and XLA's CPU backend fuses these ops into the emulation
    graph where LLVM contracts adjacent mul+sub pairs into
    single-rounding FMAs — one f32 ULP off the reference pipeline
    (which rounds each product separately) once zero-point products
    pass 2^24.  ``optimization_barrier`` does NOT reliably block the
    contraction (the emitter sees through its bitcast residue inside a
    fusion), but ``trunc`` does: it interposes a non-foldable
    intrinsic between the mul and the sub, and is an exact identity
    here because every product is mathematically an integer and the
    f32 rounding of an integer is always integer-valued (f32 spacing
    is >= 1 wherever values exceed 2^24)."""
    rowf = row.astype(jnp.float32)
    colf = col.astype(jnp.float32)
    zaf, zwf = za.astype(jnp.float32), zw.astype(jnp.float32)
    t_row = jnp.trunc(zwf * rowf[:, None])
    t_col = jnp.trunc(zaf * colf[None, :])
    t_k = jnp.trunc(k * zaf * zwf)
    acc = s - t_row - t_col + t_k
    return acc * (sa * sw)


def _lut_slot(lut_hbm, buf_ref, sem_ref, b, first_tile, n_mult):
    """Double-buffered LUT access for the banked kernels: at bank ``b``'s
    first tile, prefetch bank ``b+1``'s slice into the alternate slot
    (overlapping b's whole tile sweep) and wait on b's own copy (started
    by bank b-1's prefetch; bank 0 starts its own)."""
    slot = jax.lax.rem(b, 2)

    @pl.when(first_tile & (b == 0))
    def _seed():
        pltpu.make_async_copy(lut_hbm.at[0], buf_ref.at[0],
                              sem_ref.at[0]).start()

    @pl.when(first_tile & (b + 1 < n_mult))
    def _prefetch():
        nxt = jax.lax.rem(b + 1, 2)
        pltpu.make_async_copy(lut_hbm.at[b + 1], buf_ref.at[nxt],
                              sem_ref.at[nxt]).start()

    @pl.when(first_tile)
    def _wait():
        pltpu.make_async_copy(lut_hbm.at[b], buf_ref.at[slot],
                              sem_ref.at[slot]).wait()

    return buf_ref[slot]


# ----------------------------------------------------------------------
# Composed wide (12/16-bit) fused kernels — runtime reduce (SMEM rcode)
# ----------------------------------------------------------------------
def _digit_body(qa, qw, lut, mask, kind, kd, bm, bn):
    wide = mask != 0

    def body(c, accs):
        acc_lo, acc_hi = accs
        a_c = jax.lax.dynamic_slice(qa, (0, c * CK_CHUNK), (bm, CK_CHUNK))
        w_c = jax.lax.dynamic_slice(qw, (c * CK_CHUNK, 0), (CK_CHUNK, bn))
        a0, a1 = a_c & 255, a_c >> 8
        w0, w1 = w_c & 255, w_c >> 8

        def pp(x, y):
            idx = x[:, :, None] * 256 + y[None, :, :]
            return jnp.take(lut, idx, axis=0)

        pp00 = pp(a0, w0)
        p = composed_reduce_dyn(pp00.astype(jnp.uint32),
                                pp(a0, w1).astype(jnp.uint32),
                                pp(a1, w0).astype(jnp.uint32),
                                pp(a1, w1).astype(jnp.uint32),
                                kind, kd) & mask
        lo = jnp.where(wide, (p & jnp.uint32(0xFFFF)).astype(jnp.int32),
                       pp00)
        hi = jnp.where(wide, (p >> 16).astype(jnp.int32), 0)
        return (acc_lo + jnp.sum(lo, axis=1, dtype=jnp.int32),
                acc_hi + jnp.sum(hi, axis=1, dtype=jnp.int32))

    zeros = jnp.zeros((bm, bn), jnp.int32)
    return jax.lax.fori_loop(0, BK // CK_CHUNK, body, (zeros, zeros))


def _pad_limbs_dyn(t00, mask, kind, kd, pk):
    """Dynamic-reduce sibling of ``composed_matmul._pad_limbs``: the
    limb contribution of ``pk`` K-pad rows (codes 0) per out element."""
    p00 = composed_reduce_dyn(*(4 * (t00.astype(jnp.uint32),)),
                              kind, kd) & mask
    wide = mask != 0
    lo = jnp.where(wide, (p00 & jnp.uint32(0xFFFF)).astype(jnp.int32),
                   t00)
    hi = jnp.where(wide, (p00 >> 16).astype(jnp.int32), 0)
    return jnp.int32(pk) * lo, jnp.int32(pk) * hi


def _fused_composed_kernel(x_ref, w_ref, lut_ref, mask_ref, rc_ref,
                           fp_ref, ip_ref,
                           lo_o, hi_o, row_o, col_o,
                           lo_ref, hi_ref, row_ref, col_ref,
                           *, k, pk, nsteps, bm):
    j, k_step = pl.program_id(1), pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)
        row_ref[...] = jnp.zeros_like(row_ref)
        col_ref[...] = jnp.zeros_like(col_ref)

    sa, sw, qmax = fp_ref[0], fp_ref[1], fp_ref[2]
    za, zw = ip_ref[0], ip_ref[1]
    mask = mask_ref[0]
    kind, kd = rc_ref[0], rc_ref[1]
    qa = quant_codes(x_ref[...], sa, za, qmax)
    qw = quant_codes(w_ref[...], sw, zw, qmax)
    qa, qw = _k_masked(qa, qw, k_step, k, pk)
    row_ref[...] += jnp.sum(qa, axis=1, dtype=jnp.int32)[:, None]
    col_ref[...] += jnp.sum(qw, axis=0, dtype=jnp.int32)[None, :]
    lut = lut_ref[...]
    lo, hi = _digit_body(qa, qw, lut, mask, kind, kd, bm, qw.shape[1])
    lo_ref[...] += lo
    hi_ref[...] += hi

    @pl.when(k_step == nsteps - 1)
    def _fin():
        lo_a, hi_a = lo_ref[...], hi_ref[...]
        if pk:
            dlo, dhi = _pad_limbs_dyn(lut[0], mask, kind, kd, pk)
            lo_a, hi_a = lo_a - dlo, hi_a - dhi
        lo_o[...] = lo_a
        hi_o[...] = hi_a

    @pl.when((k_step == nsteps - 1) & (j == 0))
    def _row():
        row_o[...] = row_ref[...]

    @pl.when((k_step == nsteps - 1) & (pl.program_id(0) == 0))
    def _col():
        col_o[...] = col_ref[...]


def _fused_composed_bank_kernel(x_ref, w_ref, lut_hbm, mask_ref, rc_ref,
                                fp_ref, ip_ref,
                                lo_o, hi_o, row_o, col_o,
                                lo_ref, hi_ref, row_ref, col_ref,
                                buf_ref, sem_ref,
                                *, k, pk, nsteps, bm, n_mult, banked_a):
    b = pl.program_id(0)
    i, j = pl.program_id(1), pl.program_id(2)
    k_step = pl.program_id(3)
    first_tile = (i == 0) & (j == 0) & (k_step == 0)
    lut = _lut_slot(lut_hbm, buf_ref, sem_ref, b, first_tile, n_mult)

    @pl.when(k_step == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)
        row_ref[...] = jnp.zeros_like(row_ref)
        col_ref[...] = jnp.zeros_like(col_ref)

    sa, sw, qmax = fp_ref[b, 0], fp_ref[b, 1], fp_ref[b, 2]
    za, zw = ip_ref[b, 0], ip_ref[b, 1]
    mask = mask_ref[b]
    kind, kd = rc_ref[b, 0], rc_ref[b, 1]
    x = x_ref[...].reshape(-1, x_ref.shape[-1]) if banked_a else x_ref[...]
    qa = quant_codes(x, sa, za, qmax)
    qw = quant_codes(w_ref[...], sw, zw, qmax)
    qa, qw = _k_masked(qa, qw, k_step, k, pk)
    row_ref[...] += jnp.sum(qa, axis=1, dtype=jnp.int32)[:, None]
    col_ref[...] += jnp.sum(qw, axis=0, dtype=jnp.int32)[None, :]
    lo, hi = _digit_body(qa, qw, lut, mask, kind, kd, bm, qw.shape[1])
    lo_ref[...] += lo
    hi_ref[...] += hi

    @pl.when(k_step == nsteps - 1)
    def _fin():
        lo_a, hi_a = lo_ref[...], hi_ref[...]
        if pk:
            dlo, dhi = _pad_limbs_dyn(lut[0], mask, kind, kd, pk)
            lo_a, hi_a = lo_a - dlo, hi_a - dhi
        lo_o[...] = lo_a[None]
        hi_o[...] = hi_a[None]

    @pl.when((k_step == nsteps - 1) & (j == 0))
    def _row():
        row_o[...] = row_ref[...][None]

    @pl.when((k_step == nsteps - 1) & (i == 0))
    def _col():
        col_o[...] = col_ref[...][None]


# ----------------------------------------------------------------------
# Callers
# ----------------------------------------------------------------------
def _pack_scalars(sa, sw, qmax, za, zw, stacked: bool):
    axis = -1 if stacked else 0
    fp = jnp.stack([jnp.asarray(sa, jnp.float32),
                    jnp.asarray(sw, jnp.float32),
                    jnp.asarray(qmax, jnp.float32)], axis=axis)
    ip = jnp.stack([jnp.asarray(za, jnp.int32),
                    jnp.asarray(zw, jnp.int32)], axis=axis)
    return fp, ip


def _check_k(k: int, bound: int, what: str) -> None:
    if k > bound:
        raise ValueError(
            f"K={k} exceeds int32-safe {what} accumulation bound {bound}")


def _pad_operands(x, w, bm, banked_a):
    m, k = x.shape[-2:]
    n = w.shape[1]
    pm, pn, pk = (-m) % bm, (-n) % BN, (-k) % BK
    x_pad = ((0, 0), (0, pm), (0, pk)) if banked_a else ((0, pm), (0, pk))
    return jnp.pad(x, x_pad), jnp.pad(w, ((0, pk), (0, pn))), pk


def _bank_dequant(s, row, col, za, zw, sa, sw, k: int):
    """``_dequant`` over the bank axis, written out with explicit lane
    broadcasting — per-lane scalar op order identical to the unbanked
    path, with the same ``trunc`` anti-FMA guard on each product."""
    rowf = row.astype(jnp.float32)                      # (n, M)
    colf = col.astype(jnp.float32)                      # (n, N)
    zaf = jnp.asarray(za, jnp.int32).astype(jnp.float32)
    zwf = jnp.asarray(zw, jnp.int32).astype(jnp.float32)
    saf = jnp.asarray(sa, jnp.float32)
    swf = jnp.asarray(sw, jnp.float32)
    t_row = jnp.trunc(zwf[:, None, None] * rowf[:, :, None])
    t_col = jnp.trunc(zaf[:, None, None] * colf[:, None, :])
    t_k = jnp.trunc(k * zaf * zwf)
    acc = s - t_row - t_col + t_k[:, None, None]
    return acc * (saf * swf)[:, None, None]


def _fused_core(x, w, luts, fp, ip, interpret: bool):
    """Integer half of the banked fused datapath: per-lane weight codes
    (quantized here, in XLA) resolve the LUT columns into the one-hot
    tables, whose extra column carries the activation row sums; the
    kernel quantizes ``x`` and contracts.  Returns the (n,M,N) int32
    accumulator, the (n,M) row sums and the (n,N) weight column sums."""
    m, k = x.shape[-2:]
    n = w.shape[1]
    _check_k(k, MAX_LUT_K, "LUT")
    qw = jax.vmap(lambda s, z, q: quant_codes(w, s, z, q))(
        fp[:, 1], ip[:, 1], fp[:, 2])                        # (n,K,N)
    t_lo, t_hi = jax.vmap(
        lambda q, lut: lut_tables(q, lut, row_sums=True))(qw, luts)
    acc = lut_contract(x, t_lo, t_hi, (fp, ip), interpret=interpret)
    # the same fence as ``backend._quantized_matmul``'s around the
    # integer result: the f32 epilogue then compiles alike for every
    # datapath
    return jax.lax.optimization_barrier(
        (acc[:, :m, :n], acc[:, :m, n],
         jnp.sum(qw, axis=1, dtype=jnp.int32)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_matmul_pallas(x, w, lut, sa, za, sw, zw, qmax,
                        interpret: bool = False) -> jax.Array:
    """Fused 8-bit datapath: x (M,K) f32, w (K,N) f32, lut (256,256)
    i32, scalars from ``quant.scalar_params``.  Returns (M,N) f32 —
    bit-identical to quantize → ``approx_matmul_lut`` → correct/dequant.
    """
    fp, ip = _pack_scalars(sa, sw, qmax, za, zw, stacked=False)
    acc, row, col = _fused_core(x, w, lut[None], fp[None], ip[None],
                                interpret)
    return _dequant(acc[0].astype(jnp.float32), row[0], col[0],
                    za, zw, sa, sw, x.shape[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_matmul_bank_pallas(x, w, luts, sa, za, sw, zw, qmax,
                             interpret: bool = False) -> jax.Array:
    """Banked fused 8-bit datapath: x (M,K) shared or (n,M,K) banked
    f32; luts (n,256,256); scalars (n,) per lane.  Returns (n,M,N) f32,
    bit-identical per lane to ``fused_matmul_pallas``."""
    fp, ip = _pack_scalars(sa, sw, qmax, za, zw, stacked=True)
    acc, row, col = _fused_core(x, w, luts, fp, ip, interpret)
    return _bank_dequant(acc.astype(jnp.float32), row, col,
                         za, zw, sa, sw, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_composed_matmul_pallas(x, w, lut, mask, rcode, sa, za, sw, zw,
                                 qmax, interpret: bool = False
                                 ) -> jax.Array:
    """Fused composed wide (12/16-bit) datapath on floats: digit
    products through the 256x256 tile LUT, runtime ``rcode`` reduce
    tree (``encode_reduce``), int32 limb accumulation, f32 correction.
    mask: scalar uint32 (0 = narrow lane); rcode: (2,) int32."""
    no_mosaic("fused_composed_matmul_pallas", interpret)
    m, k = x.shape
    _, n = w.shape
    _check_k(k, MAX_COMPOSED_K, "composed limb")
    bm = _row_block(m)
    x_p, w_p, pk = _pad_operands(x, w, bm, banked_a=False)
    fp, ip = _pack_scalars(sa, sw, qmax, za, zw, stacked=False)
    nsteps = x_p.shape[1] // BK
    grid = (x_p.shape[0] // bm, w_p.shape[1] // BN, nsteps)
    mp, np_ = x_p.shape[0], w_p.shape[1]
    shape = jax.ShapeDtypeStruct((mp, np_), jnp.int32)
    lo, hi, row, col = pl.pallas_call(
        functools.partial(_fused_composed_kernel, k=k, pk=pk,
                          nsteps=nsteps, bm=bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, BK), lambda i, j, s: (i, s)),
            pl.BlockSpec((BK, BN), lambda i, j, s: (s, j)),
            pl.BlockSpec((65536,), lambda i, j, s: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((bm, BN), lambda i, j, s: (i, j)),
                   pl.BlockSpec((bm, BN), lambda i, j, s: (i, j)),
                   pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
                   pl.BlockSpec((1, BN), lambda i, j, s: (0, j))],
        out_shape=[shape, shape,
                   jax.ShapeDtypeStruct((mp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, np_), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bm, BN), jnp.int32),
                        pltpu.VMEM((bm, BN), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.int32),
                        pltpu.VMEM((1, BN), jnp.int32)],
        interpret=interpret,
    )(x_p, w_p, lut.reshape(-1),
      jnp.asarray(mask, jnp.uint32).reshape(1),
      jnp.asarray(rcode, jnp.int32).reshape(2), fp, ip)
    s = (lo[:m, :n].astype(jnp.float32)
         + 65536.0 * hi[:m, :n].astype(jnp.float32))
    return _dequant(s, row[:m, 0], col[0, :n], za, zw, sa, sw, k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_composed_matmul_bank_pallas(x, w, luts, masks, rcodes, sa, za,
                                      sw, zw, qmax,
                                      interpret: bool = False
                                      ) -> jax.Array:
    """Banked fused composed datapath: per-lane masks (n,) uint32 and
    reduce codes (n,2) int32 ride SMEM next to the per-lane quant
    scalars, so ONE program evaluates a mixed-width, mixed-reduce bank
    (n,M,N) — LUT slices DMA double-buffered from HBM."""
    no_mosaic("fused_composed_matmul_bank_pallas", interpret)
    banked_a = x.ndim == 3
    n_mult = luts.shape[0]
    m, k = x.shape[-2:]
    _, n = w.shape
    _check_k(k, MAX_COMPOSED_K, "composed limb")
    bm = _row_block(m)
    x_p, w_p, pk = _pad_operands(x, w, bm, banked_a)
    fp, ip = _pack_scalars(sa, sw, qmax, za, zw, stacked=True)
    nsteps = x_p.shape[-1] // BK
    grid = (n_mult, x_p.shape[-2] // bm, w_p.shape[1] // BN, nsteps)
    if banked_a:
        x_spec = pl.BlockSpec((1, bm, BK), lambda b, i, j, s: (b, i, s))
    else:
        x_spec = pl.BlockSpec((bm, BK), lambda b, i, j, s: (i, s))
    mp, np_ = x_p.shape[-2], w_p.shape[1]
    shape = jax.ShapeDtypeStruct((n_mult, mp, np_), jnp.int32)
    lo, hi, row, col = pl.pallas_call(
        functools.partial(_fused_composed_bank_kernel, k=k, pk=pk,
                          nsteps=nsteps, bm=bm, n_mult=n_mult,
                          banked_a=banked_a),
        grid=grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((BK, BN), lambda b, i, j, s: (s, j)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, BN), lambda b, i, j, s: (b, i, j)),
            pl.BlockSpec((1, bm, BN), lambda b, i, j, s: (b, i, j)),
            pl.BlockSpec((1, bm, 1), lambda b, i, j, s: (b, i, 0)),
            pl.BlockSpec((1, 1, BN), lambda b, i, j, s: (b, 0, j))],
        out_shape=[shape, shape,
                   jax.ShapeDtypeStruct((n_mult, mp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n_mult, 1, np_), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bm, BN), jnp.int32),
                        pltpu.VMEM((bm, BN), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.int32),
                        pltpu.VMEM((1, BN), jnp.int32),
                        pltpu.VMEM((2, 65536), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(x_p, w_p, luts.reshape(n_mult, -1),
      jnp.asarray(masks, jnp.uint32).reshape(n_mult),
      jnp.asarray(rcodes, jnp.int32).reshape(n_mult, 2), fp, ip)
    s = (lo[:, :m, :n].astype(jnp.float32)
         + 65536.0 * hi[:, :m, :n].astype(jnp.float32))
    return _bank_dequant(s, row[:, :m, 0], col[:, 0, :n],
                         za, zw, sa, sw, k)
