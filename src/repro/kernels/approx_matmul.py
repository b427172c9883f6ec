"""Pallas TPU kernels: bit-true LUT approximate matmul as a one-hot MXU
contraction — single LUT and banked.

TPU-native port of TFApprox's GPU texture-LUT emulation (DESIGN.md
§4.5).  Mosaic lowers no gather from a 64K-entry table, so the lookup
``out[m,n] = Σ_k LUT[a[m,k], w[k,n]]`` is rewritten as a matmul that is
exact by construction:

* The weight side is resolved once per call, outside the kernel
  (``lut_tables``, an XLA row gather of the 256x256 LUT):
  ``T[k*256 + v, n] = LUT[v, w[k,n]]``, split into byte planes
  ``T = T_lo + 256*T_hi`` whose entries lie in [0, 255] — exact in
  bfloat16.
* In the kernel every activation code becomes a one-hot row over its
  256-wide group, ``oh[m, k*256 + v] = (a[m,k] == v)``, and the sum is
  ``oh @ T_lo + 256 * (oh @ T_hi)`` on the MXU with f32 accumulation.

Each grid step contracts ``SUB`` = 8 codes (``GROUP`` = 2048 one-hot
columns): at most 8 nonzero terms per output, each below 2^16, so the
f32 step result (< 2^19) is an exact integer and is accumulated in
int32 across steps.  The result is bit-identical to the gather oracle
(``ref.approx_matmul_lut_ref``) for LUT entries in [0, 2^16) — the
range of every 8x8 product LUT.

The one-hot needs no lane gather either: the (bm, 128) code block is
multiplied by a 0/1 replication matrix that copies code ``8c' + g`` to
lanes [256g, 256g + 256) (exact: codes <= 255 are exact in bf16), and
the copy is compared with the lane index mod 256.

K-padding rows of T are zero, so padded codes contribute nothing.  The
banked kernel puts the ``LutBank`` lane axis first in the grid, with one
pair of table planes per lane; activations are either shared (M,K) or
banked (n,M,K).  The single-LUT kernel is the one-lane bank.

VMEM per grid step (bm=256): codes 2x128K + table planes 2x2x512K +
replication matrix 512K + replicated codes 2M + one-hot 1M + output
2x128K ≈ 6 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: codes contracted per grid step, and the one-hot columns they span
SUB = 8
GROUP = SUB * 256
#: code-block width (one lane tile); its SUB-wide slices are the steps
KB = 128
#: output lane block and the largest row block (a multiple of 16, the
#: bf16 sublane tile of the one-hot)
BN = 128
BM = 256


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _row_block(m: int) -> int:
    """Row block for M rows: 256, or M rounded up to 16 when smaller."""
    return min(BM, _round_up(m, 16))


def lut_tables(qw: jax.Array, lut: jax.Array, row_sums: bool = False):
    """Weight-side byte-plane tables of the one-hot contraction.

    qw: (K,N) int32 codes in [0,255]; lut: (256,256) int32 with entries
    in [0, 2^16).  Returns ``(t_lo, t_hi)``, each
    ``(ceil(K/SUB)*GROUP, Np)`` bf16 with Np = N (+1) rounded up to
    ``BN``, where ``t_lo + 256*t_hi`` at row ``k*256 + v``, column n is
    ``LUT[v, qw[k,n]]``.  ``row_sums=True`` adds column N holding ``v``
    itself, so the contraction also yields ``Σ_k a[m,k]``.  The ops
    carry the name scope ``lut_tables``."""
    k, n = qw.shape
    kp = _round_up(k, SUB)
    np_ = _round_up(n + int(row_sums), BN)
    with jax.named_scope("lut_tables"):
        t = jnp.take(lut, qw, axis=1)               # (256, K, N)
        t = jnp.transpose(t, (1, 0, 2))             # (K, 256, N)
        if row_sums:
            v = jnp.arange(256, dtype=jnp.int32)[None, :, None]
            t = jnp.concatenate([t, jnp.broadcast_to(v, (k, 256, 1))],
                                axis=2)
        t = jnp.pad(t, ((0, kp - k), (0, 0), (0, np_ - t.shape[2])))
        t = t.reshape(kp * 256, np_)
        return (t & 255).astype(jnp.bfloat16), \
            (t >> 8).astype(jnp.bfloat16)


def _replication(c):
    """(KB, GROUP) 0/1 matrix sending code ``SUB*(c mod KB/SUB) + g`` of
    a code block to one-hot columns [256g, 256g + 256)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (KB, GROUP), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (KB, GROUP), 1)
    base = jax.lax.rem(c, KB // SUB) * SUB
    return (row == base + (col >> 8)).astype(jnp.bfloat16)


def _onehot_step(codes, c, t_lo, t_hi):
    """Exact Σ over the SUB codes of step ``c``: codes (bm, KB) int32 in
    [0,255]; t_lo/t_hi (GROUP, BN) bf16 -> (bm, BN) int32."""
    rep = jnp.dot(codes.astype(jnp.float32).astype(jnp.bfloat16),
                  _replication(c), preferred_element_type=jnp.float32)
    v = (jax.lax.broadcasted_iota(jnp.int32, (1, GROUP), 1)
         & 255).astype(jnp.float32)
    onehot = (rep == v).astype(jnp.bfloat16)
    lo = jnp.dot(onehot, t_lo, preferred_element_type=jnp.float32)
    hi = jnp.dot(onehot, t_hi, preferred_element_type=jnp.float32)
    return (lo + 256.0 * hi).astype(jnp.int32)


def quant_codes(v, scale, zp, qmax):
    """``repro.approx.quant.quantize`` with explicit scalars — identical
    op/dtype order (round, +int32 zp in f32, clip, cast)."""
    q = jnp.round(v / scale) + zp
    return jnp.clip(q, 0, qmax).astype(jnp.int32)


def _kernel(a_ref, lo_ref, hi_ref, *rest, quant: bool):
    if quant:
        fp_ref, ip_ref, o_ref = rest
    else:
        (o_ref,) = rest
    b, c = pl.program_id(0), pl.program_id(3)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = a_ref[...]
    if quant:
        codes = quant_codes(codes, fp_ref[b, 0], ip_ref[b, 0], fp_ref[b, 2])
    o_ref[...] += _onehot_step(codes, c, lo_ref[...], hi_ref[...])


def lut_contract(a, t_lo, t_hi, qparams=None, *, interpret: bool):
    """The banked one-hot contraction: ``(n, Mp, Np)`` int32 with
    ``out[b] = Σ_k T_b[a_b[m,k]]`` (padding rows/columns included).

    a: (M,K) shared or (n,M,K) banked int32 codes — or float operands
    when ``qparams = (fp, ip)`` gives each lane's quantization scalars
    ((n,3) f32 ``[sa, sw, qmax]`` and (n,2) int32 ``[za, zw]``, SMEM),
    quantized in the kernel.  t_lo/t_hi: (n, rows, Np) from
    ``lut_tables``."""
    n_mult, rows, np_ = t_lo.shape
    banked_a = a.ndim == 3
    m, k = a.shape[-2:]
    bm = _row_block(m)
    pad = [(0, _round_up(m, bm) - m), (0, _round_up(k, KB) - k)]
    a = jnp.pad(a, ([(0, 0)] if banked_a else []) + pad)
    mp = a.shape[-2]
    steps = KB // SUB
    grid = (n_mult, mp // bm, np_ // BN, rows // GROUP)
    if banked_a:
        a_spec = pl.BlockSpec((None, bm, KB),
                              lambda b, i, j, c: (b, i, c // steps))
    else:
        a_spec = pl.BlockSpec((bm, KB), lambda b, i, j, c: (i, c // steps))
    t_spec = pl.BlockSpec((None, GROUP, BN), lambda b, i, j, c: (b, c, j))
    in_specs = [a_spec, t_spec, t_spec]
    args = [a, t_lo, t_hi]
    if qparams is not None:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        args += list(qparams)
    return pl.pallas_call(
        functools.partial(_kernel, quant=qparams is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bm, BN),
                               lambda b, i, j, c: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_mult, mp, np_), jnp.int32),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def approx_matmul_lut_bank_pallas(qa: jax.Array, qw: jax.Array,
                                  luts: jax.Array,
                                  interpret: bool = False) -> jax.Array:
    """qa: (M,K) or (n,M,K) int32 in [0,255]; qw: (K,N) int32;
    luts: (n,256,256) int32.  Returns (n,M,N) int32 where
    ``out[b] = Σ_k luts[b][qa_b, qw]`` (``qa_b = qa`` when shared)."""
    m, k = qa.shape[-2:]
    k2, n = qw.shape
    assert k == k2
    assert qa.ndim == 2 or qa.shape[0] == luts.shape[0]
    t_lo, t_hi = jax.vmap(lambda lut: lut_tables(qw, lut))(luts)
    out = lut_contract(qa, t_lo, t_hi, interpret=interpret)
    return out[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def approx_matmul_lut_pallas(qa: jax.Array, qw: jax.Array, lut: jax.Array,
                             interpret: bool = False) -> jax.Array:
    """qa: (M,K) int32 in [0,255]; qw: (K,N) int32; lut: (256,256) int32.
    Returns (M,N) int32 = Σ_k LUT[qa, qw] — the one-lane bank."""
    return approx_matmul_lut_bank_pallas(qa, qw, lut[None],
                                         interpret=interpret)[0]
