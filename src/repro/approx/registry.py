"""Pluggable datapath registry (DESIGN.md §2.1).

A *datapath* is the arithmetic core of the accelerator being emulated:
given uint8 operand codes it returns the raw accumulated products
``Σ_k mul(qa[m,k], qw[k,n])``.  Zero-point correction, scaling and the
straight-through gradient wrapper live in ``repro.approx.backend`` and
are shared by every datapath, so registering a new datapath is the ONLY
step needed to plug a new emulation strategy (Booth/stochastic circuits,
per-layer rank schedules, ...) into every model, sweep and serve path.

Built-in datapaths registered here:

  * ``int8``    — exact uint8 datapath (the paper's golden reference);
                  int32-exact correction arithmetic
  * ``lut``     — bit-true 256x256 LUT emulation (TFApprox port)
  * ``lowrank`` — rank-R factored LUT: R table lookups + R MXU matmuls

Pallas variants (``lut_pallas``, ``lut_fused``; ``lowrank_pallas`` is
the ``lowrank`` datapath under that name) are registered by
``repro.kernels.datapaths`` and resolved lazily on first lookup, so the
core package never imports the kernel layer eagerly.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

MAX_LUT_K = 33030  # int32-safe accumulation bound: 2^31 / 255^2
# Composed wide products accumulate as two 16-bit limbs (DESIGN.md
# §2.6): each limb is < 2^16, so int32 limb sums stay exact for up to
# 2^31 / (2^16 - 1) contraction terms.
MAX_COMPOSED_K = (1 << 31) // ((1 << 16) - 1)  # = 32768


class Datapath:
    """Protocol/base class for registered datapaths.

    ``pack(spec, library)`` runs once per (spec, library) on the host and
    returns the device-constant dict consumed by ``forward_q``; the
    result is cached by ``repro.approx.specs.materialize``.
    ``exact_int32`` datapaths return int32 sums whose zero-point
    correction must stay in int32 (bit-exact); the rest are corrected in
    float32.  ``needs_library`` controls whether materialization binds
    the consts to a specific ``ApproxLibrary``.
    """

    name: str = "?"
    exact_int32: bool = False
    needs_library: bool = True
    # True for single-program datapaths (DESIGN.md §2.10): the backend
    # hands them the FLOAT operands via ``forward_fused(x2d, w, consts)``
    # and they calibrate/quantize/gather/dequant inside one kernel —
    # ``forward_q`` (codes in, raw sums out) is never called.
    fused: bool = False
    # spec fields this datapath actually reads in pack()/forward_q();
    # fields outside this set are canonicalized away in cache keys so
    # equivalent configurations share one materialization + jit trace.
    spec_fields: tuple = ("multiplier", "rank", "block_m")
    # True when forward_q stays correct (and efficient) with a vmapped
    # per-multiplier LUT const — the batched resilience engine only
    # banks datapaths that declare it (DESIGN.md §2.4).
    bankable: bool = False

    def pack(self, spec, library) -> dict:
        return {}

    def forward_q(self, qa: jax.Array, qw: jax.Array, consts: dict
                  ) -> jax.Array:
        raise NotImplementedError


_REGISTRY: dict[str, Datapath] = {}


def register_datapath(name: str) -> Callable[[type], type]:
    """Class decorator: instantiate and register under ``name``."""
    def deco(cls: type) -> type:
        inst = cls()
        inst.name = name
        _REGISTRY[name] = inst
        return cls
    return deco


def get_datapath(name: str) -> Datapath:
    if name not in _REGISTRY and name.endswith(("_pallas", "_fused")):
        # Pallas variants live in the kernel layer; import on demand.
        import repro.kernels.datapaths  # noqa: F401  (registers on import)
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown datapath {name!r}; available: "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_datapaths() -> list[str]:
    return sorted(_REGISTRY)


# ----------------------------------------------------------------------
# Shared pack helpers
# ----------------------------------------------------------------------
def _resolve_rank(spec, library, lut: np.ndarray) -> int:
    """spec.rank, or the smallest R whose decomposition error is
    negligible next to the circuit's own error (floor 0.25 LSB^2)."""
    from repro.core.luts import rank_for_tolerance
    if spec.rank:
        return int(spec.rank)
    mult_mae = max(library.entry(spec.multiplier).errors.mae, 0.0)
    tol = max(0.25, 0.1 * mult_mae)
    return int(rank_for_tolerance(lut, tol, max_rank=16))


def _validate_reduce(spec, comp) -> tuple:
    """The parsed reduction of the entry's composition recipe, checked
    against the spec's ``reduce_adder`` declaration when present."""
    from repro.core.families import parse_reduce
    reduce = parse_reduce(comp["reduce"])
    declared = getattr(spec, "reduce_adder", None)
    if declared is not None and parse_reduce(declared) != reduce:
        raise ValueError(
            f"spec declares reduce_adder={declared!r} but composed "
            f"entry {spec.multiplier!r} reduces with "
            f"{comp['reduce']!r}")
    return reduce


def pack_lut(spec, library) -> dict:
    """Device consts for the (width-generic) LUT datapaths.

    8-bit entries pack their own 256x256 LUT (the historical path,
    bit-identical).  Composed wide entries pack the composition TILE's
    256x256 LUT plus the composition descriptor — operand width
    (``bits``), the static ``composed`` dispatch flag, the per-lane
    ``wide`` selector and the parsed ``reduce`` tree — which the
    composed engines (ref + Pallas) consume (DESIGN.md §2.6).
    """
    entry = library.entry(spec.multiplier,
                          bit_width=getattr(spec, "bit_width", None))
    comp = library.composition_of(spec.multiplier)
    lut = np.asarray(library.tile_lut(spec.multiplier), dtype=np.int32)
    consts = {"lut": lut, "block_m": int(spec.block_m)}
    if comp is not None:
        consts.update(composed=True, bits=int(entry.width),
                      mask=int(lane_mask_np(entry.width)),
                      reduce=_validate_reduce(spec, comp))
    elif getattr(spec, "reduce_adder", None) is not None:
        raise ValueError(
            f"reduce_adder={spec.reduce_adder!r} is only meaningful "
            f"for composed wide entries; {spec.multiplier!r} is "
            f"{entry.width}-bit and materializes directly")
    return consts


def pack_lowrank(spec, library) -> dict:
    from repro.core.luts import decompose_lut
    lut = np.asarray(library.lut(spec.multiplier), dtype=np.int32)
    fac = decompose_lut(lut, _resolve_rank(spec, library, lut))
    return {"u": np.asarray(fac.u), "v": np.asarray(fac.v)}


# ----------------------------------------------------------------------
# Built-in datapaths
# ----------------------------------------------------------------------
@register_datapath("int8")
class Int8Datapath(Datapath):
    """Exact Σ qa·qw with int32 accumulation (golden 8-bit datapath).

    The codes are 8-bit, so the dot takes uint8 operands: the same
    exact integer result as an int32 x int32 dot, which a TPU has to
    emulate at several times the compile time."""

    exact_int32 = True
    needs_library = False
    spec_fields = ()

    def forward_q(self, qa, qw, consts):
        return jax.lax.dot_general(
            qa.astype(jnp.uint8), qw.astype(jnp.uint8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )


def _lut_columns(qw: jax.Array, lut) -> jax.Array:
    """The weight side of the lookup, resolved once per matmul:
    (K*256, N) i32 whose row ``k*256 + v`` is ``LUT[v, qw[k, :]]``."""
    k, n = qw.shape
    lut = jnp.asarray(lut, dtype=jnp.int32).reshape(256, 256)
    cols = jnp.take(lut, qw, axis=1)                        # (256,K,N)
    return jnp.transpose(cols, (1, 0, 2)).reshape(k * 256, n)


def _lut_gather_block(qa_blk: jax.Array, cols: jax.Array) -> jax.Array:
    """Σ_k LUT[qa, qw] for one row block: (mb,K) codes -> (mb,N) i32.
    Each (m, k) gathers one N-wide row of ``_lut_columns`` — N times
    fewer gather indices than a gather per product, which is what a
    TPU's gather throughput is bound by."""
    k = qa_blk.shape[1]
    rows = qa_blk + 256 * jnp.arange(k, dtype=jnp.int32)    # (mb,K)
    prods = jnp.take(cols, rows, axis=0)                    # (mb,K,N) i32
    return jnp.sum(prods, axis=1, dtype=jnp.int32)


# ----------------------------------------------------------------------
# Composed wide products: tiled 8x8 partial products + shift/add tree
# (DESIGN.md §2.6).  Shared by the ref datapath and the Pallas kernels.
# ----------------------------------------------------------------------
def reduce_apply(a: jax.Array, b: jax.Array, reduce: tuple) -> jax.Array:
    """One reduction-tree adder on uint32 values — the vectorized
    semantics of the library's adder families, bit-identical to the
    gate-level generators in ``repro.core.families`` (every tree node
    value fits its netlist adder's width, so no wraparound diverges).
    """
    kind, k = reduce
    if kind == "exact":
        return a + b
    if kind == "trunc":
        return ((a >> k) + (b >> k)) << k
    if kind == "loa":
        mask = jnp.uint32((1 << k) - 1)
        carry = (a >> (k - 1)) & (b >> (k - 1)) & jnp.uint32(1)
        return ((a | b) & mask) | ((((a >> k) + (b >> k)) + carry) << k)
    raise ValueError(f"unknown reduction kind {kind!r}")


def composed_reduce(pp00, pp01, pp10, pp11, reduce: tuple) -> jax.Array:
    """uint32 shift/add tree over the four digit products:
    ``p = ADD(ADD(pp00, ADD(pp01, pp10) << 8), pp11 << 16)`` — the
    same tree ``repro.core.families.composed_multiplier`` builds in
    gates.  NOTE: the gate netlist keeps only the low 2W output bits;
    callers must apply ``product_mask(bits)`` to match it (a W=12
    tile that over-estimates can push the tree past 2^24)."""
    s1 = reduce_apply(pp01, pp10, reduce)
    s2 = reduce_apply(pp00, s1 << 8, reduce)
    return reduce_apply(s2, pp11 << 16, reduce)


#: Order fixing the integer encoding of reduction kinds for the fused
#: kernels (``encode_reduce``); index == wire value.
REDUCE_KINDS = ("exact", "trunc", "loa")


def encode_reduce(reduce: tuple) -> tuple[int, int]:
    """A parsed ``(kind, k)`` reduction as two small ints — the runtime
    encoding the fused kernels consume (DESIGN.md §2.10).  Making the
    adder family DATA instead of a static kernel parameter is what
    collapses per-reduce program splits: one compiled fused program
    serves every adder family, so mixed-reduce banks stay O(1)."""
    kind, k = reduce
    if kind not in REDUCE_KINDS:
        raise ValueError(f"unknown reduction kind {kind!r}")
    return (REDUCE_KINDS.index(kind), int(k))


def reduce_apply_dyn(a: jax.Array, b: jax.Array, kind: jax.Array,
                     k: jax.Array) -> jax.Array:
    """``reduce_apply`` with the reduction selected by runtime scalars
    ``(kind, k)`` (see ``encode_reduce``).  All three adder families are
    computed and selected — integer ops, so each branch's value is
    bit-identical to its static sibling (the price is ~3x the adder
    ALU work, negligible next to the digit-product gathers)."""
    kind = jnp.asarray(kind, jnp.int32)
    k = jnp.asarray(k, jnp.uint32)
    exact = a + b
    hs = ((a >> k) + (b >> k))
    trunc = hs << k
    km = jnp.maximum(k, jnp.uint32(1))       # loa guard: k >= 1 by parse
    mask = (jnp.uint32(1) << km) - jnp.uint32(1)
    carry = (a >> (km - 1)) & (b >> (km - 1)) & jnp.uint32(1)
    loa = ((a | b) & mask) | ((hs + carry) << k)
    return jnp.where(kind == 0, exact, jnp.where(kind == 1, trunc, loa))


def composed_reduce_dyn(pp00, pp01, pp10, pp11, kind, k) -> jax.Array:
    """``composed_reduce`` with a runtime-selected adder family — the
    same shift/add tree, every node through ``reduce_apply_dyn``."""
    s1 = reduce_apply_dyn(pp01, pp10, kind, k)
    s2 = reduce_apply_dyn(pp00, s1 << 8, kind, k)
    return reduce_apply_dyn(s2, pp11 << 16, kind, k)


def product_mask(bits) -> jax.Array:
    """uint32 mask keeping the composed netlist's 2W output bits
    (``0xFFFFFF`` at W=12, ``0xFFFFFFFF`` at W=16).  Traceable in
    ``bits``; computed as a right-shift of all-ones so no shift ever
    reaches the full register width."""
    if isinstance(bits, int):
        return jnp.uint32((1 << (2 * bits)) - 1 if bits < 16
                          else 0xFFFFFFFF)
    shift = (32 - 2 * jnp.asarray(bits, jnp.uint32))
    return jnp.uint32(0xFFFFFFFF) >> shift


def lane_mask_np(bits) -> np.ndarray:
    """Host-side per-lane selector-and-mask of the banked composed
    engine: 0 for narrow (8-bit) lanes — "take the plain tile sum" —
    and the 2W-bit ``product_mask`` for wide lanes.  The single source
    of the bits→mask rule for ``pack_lut`` and ``LutBank.lane_masks``
    (``product_mask`` is its traced sibling for in-graph widths)."""
    bits = np.asarray(bits, np.int64)
    masks = np.where(bits >= 16, 0xFFFFFFFF, (1 << (2 * bits)) - 1)
    return np.where(bits > 8, masks, 0).astype(np.uint32)


def composed_product(qa: jax.Array, qw: jax.Array, flat_lut: jax.Array,
                     reduce: tuple, bits: int = 16) -> jax.Array:
    """Elementwise composed product of W-bit codes (any broadcastable
    shapes) as exact uint32, truncated to the netlist's 2W output bits
    — the scalar semantics the bitsim oracle tests pin down."""
    def pp(x, y):
        return jnp.take(flat_lut, x * 256 + y, axis=0).astype(jnp.uint32)
    a0, a1 = qa & 255, qa >> 8
    w0, w1 = qw & 255, qw >> 8
    return composed_reduce(pp(a0, w0), pp(a0, w1), pp(a1, w0),
                           pp(a1, w1), reduce) & product_mask(bits)


def _composed_gather_block(qa_blk: jax.Array, qw: jax.Array,
                           flat_lut: jax.Array, mask, reduce: tuple
                           ) -> jax.Array:
    """Composed-product row block: (mb,K) x (K,N) -> (mb,N) f32.

    Wide products are truncated to the lane's ``mask`` (the netlist's
    2W output bits), split into two 16-bit limbs accumulated exactly
    in int32 (``K <= MAX_COMPOSED_K``), then recombined in f32.
    ``mask == 0`` marks a narrow lane: it takes the plain 8-bit tile
    sum (`pp00` alone), which keeps narrow lanes of a mixed-width bank
    bit-identical to the historical 8-bit path."""
    a0, a1 = qa_blk & 255, qa_blk >> 8
    w0, w1 = qw & 255, qw >> 8
    mask = jnp.asarray(mask, jnp.uint32)

    def pp(x, y):                                        # (mb,K,N) i32
        idx = x[:, :, None] * 256 + y[None, :, :]
        return jnp.take(flat_lut, idx, axis=0)

    pp00 = pp(a0, w0)
    p = composed_reduce(pp00.astype(jnp.uint32),
                        pp(a0, w1).astype(jnp.uint32),
                        pp(a1, w0).astype(jnp.uint32),
                        pp(a1, w1).astype(jnp.uint32), reduce) & mask
    lo = (p & jnp.uint32(0xFFFF)).astype(jnp.int32)
    hi = (p >> 16).astype(jnp.int32)
    s_lo = jnp.sum(lo, axis=1, dtype=jnp.int32).astype(jnp.float32)
    s_hi = jnp.sum(hi, axis=1, dtype=jnp.int32).astype(jnp.float32)
    s00 = jnp.sum(pp00, axis=1, dtype=jnp.int32).astype(jnp.float32)
    return jnp.where(mask != 0, s_lo + 65536.0 * s_hi, s00)


def composed_forward(qa: jax.Array, qw: jax.Array, lut: jax.Array,
                     mask, reduce: tuple, block_m: int) -> jax.Array:
    """Blocked composed matmul on codes (ref datapath core):
    (M,K) x (K,N) -> (M,N) f32."""
    m, k = qa.shape
    if k > MAX_COMPOSED_K:
        raise ValueError(
            f"K={k} exceeds int32-safe composed limb accumulation "
            f"bound {MAX_COMPOSED_K}")
    flat = jnp.asarray(lut, dtype=jnp.int32).reshape(-1)
    mb = min(block_m, m)
    pad = (-m) % mb
    qa_p = jnp.pad(qa, ((0, pad), (0, 0)))
    blocks = qa_p.reshape(-1, mb, k)
    out = jax.lax.map(
        lambda blk: _composed_gather_block(blk, qw, flat, mask, reduce),
        blocks)
    return out.reshape(-1, out.shape[-1])[:m]


@register_datapath("lut")
class LutDatapath(Datapath):
    """Blocked bit-true LUT matmul on codes — width-generic.

    8-bit (``composed`` unset): (M,K) x (K,N) -> (M,N) i32, the
    historical bit-identical path.  Composed wide (DESIGN.md §2.6):
    digit products through the 256x256 TILE LUT, reduced by the
    spec'd shift/add tree, limb-accumulated -> (M,N) f32.
    """

    spec_fields = ("multiplier", "block_m", "bit_width", "reduce_adder")
    bankable = True

    def pack(self, spec, library) -> dict:
        return pack_lut(spec, library)

    def forward_q(self, qa, qw, consts):
        m, k = qa.shape
        if consts.get("composed"):
            return composed_forward(qa, qw, consts["lut"],
                                    consts["mask"], consts["reduce"],
                                    min(consts["block_m"], m))
        if k > MAX_LUT_K:
            raise ValueError(
                f"K={k} exceeds int32-safe LUT accumulation bound")
        cols = _lut_columns(qw, consts["lut"])
        mb = min(consts["block_m"], m)
        pad = (-m) % mb
        qa_p = jnp.pad(qa, ((0, pad), (0, 0)))
        blocks = qa_p.reshape(-1, mb, k)
        out = jax.lax.map(lambda blk: _lut_gather_block(blk, cols), blocks)
        return out.reshape(-1, out.shape[-1])[:m]


@register_datapath("lowrank")
class LowRankDatapath(Datapath):
    """Σ_k Σ_r U[r,qa]V[r,qw]  ==  Σ_r tableU_r(qa) @ tableV_r(qw).
    (M,K) x (K,N) -> (M,N) f32; R batched MXU matmuls."""

    spec_fields = ("multiplier", "rank")

    def pack(self, spec, library) -> dict:
        return pack_lowrank(spec, library)

    def forward_q(self, qa, qw, consts):
        u = jnp.asarray(consts["u"])
        v = jnp.asarray(consts["v"])
        ua = jnp.take(u, qa, axis=1)   # (R,M,K) f32
        vw = jnp.take(v, qw, axis=1)   # (R,K,N) f32
        return jnp.einsum("rmk,rkn->mn", ua, vw,
                          preferred_element_type=jnp.float32)
