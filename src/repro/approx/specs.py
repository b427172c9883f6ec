"""Serializable backend specs + cached materialization (DESIGN.md §2.2).

``BackendSpec`` is the *name* of an accelerator datapath configuration:
a frozen, value-hashable, JSON round-trippable record (mode, multiplier,
rank, blocking, STE, kernel variant).  It carries no arrays, so it can
live in configs, checkpoints, serve requests and cache keys.

``spec.materialize(library)`` binds the spec to a concrete
``ApproxLibrary`` and returns a ``MaterializedBackend`` holding the
packed device constants (LUTs / low-rank factors).  Materialization is
LRU-cached per (library, spec): resilience sweeps and the serve engine
that reference the same multiplier twice get the SAME backend object
back, so downstream ``jax.jit`` tracing caches hit instead of
re-tracing per backend instance (the failure mode of the legacy
id-hashed ``MatmulBackend``).
"""
from __future__ import annotations

import json
import weakref
from collections import OrderedDict
from dataclasses import asdict, dataclass, field, replace
from typing import Any, Mapping, Optional

import numpy as np

from .registry import Datapath, get_datapath

_EXACT_MODES = ("f32", "bf16")
_VARIANTS = ("ref", "pallas", "fused")


@dataclass(frozen=True)
class BackendSpec:
    """Value-hashable description of one emulated datapath.

    ``mode`` selects the registered datapath ("f32"/"bf16" bypass
    quantization entirely); ``variant`` selects the kernel
    implementation ("ref" = jnp reference, "pallas" = Pallas kernel).
    ``rank=None`` means auto (smallest R with negligible decomposition
    error, resolved at pack time).

    Width-generic datapaths (DESIGN.md §2.6): ``bit_width`` declares
    the multiplier's operand width (None = infer from the library
    entry; a set value is VALIDATED against the entry at pack time),
    and ``reduce_adder`` optionally declares the composed shift/add
    tree's adder family ("exact", "loa4", "trunc3", or a library adder
    name) — also validated against the composed entry's recipe, so a
    policy JSON carries the full datapath description self-contained.
    """

    mode: str = "bf16"
    multiplier: str = "mul8u_exact"
    rank: Optional[int] = None
    block_m: int = 512
    ste: bool = True
    variant: str = "ref"
    bit_width: Optional[int] = None
    reduce_adder: Optional[str] = None

    def __post_init__(self):
        if self.variant not in _VARIANTS:
            raise ValueError(f"variant must be one of {_VARIANTS}, "
                             f"got {self.variant!r}")
        if self.bit_width is not None and not 8 <= self.bit_width <= 16:
            raise ValueError(
                f"bit_width must be in [8, 16] (8-bit direct LUTs, "
                f"composed tiles above), got {self.bit_width}")
        if self.reduce_adder is not None:
            from repro.core.families import parse_reduce
            parse_reduce(self.reduce_adder)   # raises on bad tokens

    # -- constructors ---------------------------------------------------
    @staticmethod
    def exact(mode: str = "bf16") -> "BackendSpec":
        return BackendSpec(mode=mode)

    @staticmethod
    def golden() -> "BackendSpec":
        """The paper's exact 8-bit reference datapath."""
        return BackendSpec(mode="int8")

    @staticmethod
    def from_library(multiplier: str, mode: str = "lut",
                     rank: Optional[int] = None,
                     variant: str = "ref",
                     bit_width: Optional[int] = None) -> "BackendSpec":
        return BackendSpec(mode=mode, multiplier=multiplier, rank=rank,
                           variant=variant, bit_width=bit_width)

    # -- derived --------------------------------------------------------
    @property
    def is_quantized(self) -> bool:
        return self.mode not in _EXACT_MODES

    @property
    def datapath_name(self) -> str:
        return (self.mode if self.variant == "ref"
                else f"{self.mode}_{self.variant}")

    def with_(self, **changes) -> "BackendSpec":
        return replace(self, **changes)

    # -- serialization --------------------------------------------------
    def to_dict(self) -> dict:
        return asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @staticmethod
    def from_dict(d: Mapping[str, Any]) -> "BackendSpec":
        known = {f for f in BackendSpec.__dataclass_fields__}
        extra = set(d) - known
        if extra:
            raise ValueError(f"unknown BackendSpec fields: {sorted(extra)}")
        return BackendSpec(**dict(d))

    @staticmethod
    def from_json(s: str) -> "BackendSpec":
        return BackendSpec.from_dict(json.loads(s))

    # -- materialization ------------------------------------------------
    def materialize(self, library=None) -> "MaterializedBackend":
        """Bind to ``library`` through the process-wide LRU cache: equal
        (canonicalized) specs get the SAME backend object back, which is
        what lets sequential sweeps share one jit trace per multiplier.
        Batched sweeps bypass per-spec materialization entirely — the
        whole candidate axis packs into one ``LutBank`` instead."""
        return materialize(self, library)


@dataclass(frozen=True, eq=False)  # id-hash: cache guarantees uniqueness
class MaterializedBackend:
    """A spec bound to packed device constants.  ``canonical`` marks
    instances built by ``materialize`` (consts derived from the spec +
    a library) — only those may be identified by spec alone in policy
    cache keys; ad-hoc wrappers around hand-attached arrays are not."""

    spec: BackendSpec
    datapath: Optional[Datapath]       # None for f32/bf16
    consts: Mapping[str, Any] = field(default_factory=dict)
    canonical: bool = False

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def ste(self) -> bool:
        return self.spec.ste

    @property
    def multiplier(self) -> str:
        return self.spec.multiplier

    @property
    def rank(self) -> int:
        """Effective rank after auto-resolution (0 if not low-rank)."""
        u = self.consts.get("u")
        return int(u.shape[0]) if u is not None else int(self.spec.rank or 0)


# ----------------------------------------------------------------------
# Materialization cache
# ----------------------------------------------------------------------
_CACHE: "OrderedDict[tuple[int, BackendSpec], MaterializedBackend]" = \
    OrderedDict()
_CACHE_MAX = 256
_FINALIZED: set[int] = set()
_STATS = {"hits": 0, "misses": 0}


def _evict_library(lid: int) -> None:
    _FINALIZED.discard(lid)
    for k in [k for k in _CACHE if k[0] == lid]:
        del _CACHE[k]
    for k in [k for k in _BANK_CACHE if k[0] == lid]:
        del _BANK_CACHE[k]


def _library_key(library) -> int:
    lid = id(library)
    if lid not in _FINALIZED:
        _FINALIZED.add(lid)
        # evict on library GC so a recycled id can never alias
        weakref.finalize(library, _evict_library, lid)
    return lid


_SPEC_FIELD_DEFAULTS = {"multiplier": "mul8u_exact", "rank": None,
                        "block_m": 512, "bit_width": None,
                        "reduce_adder": None}


def canonicalize(spec: BackendSpec) -> BackendSpec:
    """Reset fields the spec's datapath never reads to their defaults,
    so equivalent configurations share one materialization / cache key
    (e.g. every int8 spec collapses to ``BackendSpec.golden()``).
    Serialization keeps the full spec; only caches canonicalize."""
    if not spec.is_quantized:
        return replace(spec, variant="ref", **_SPEC_FIELD_DEFAULTS)
    try:
        dp = get_datapath(spec.datapath_name)
    except KeyError:
        return spec
    relevant = getattr(dp, "spec_fields",
                       tuple(_SPEC_FIELD_DEFAULTS))
    changes = {f: d for f, d in _SPEC_FIELD_DEFAULTS.items()
               if f not in relevant and getattr(spec, f) != d}
    return replace(spec, **changes) if changes else spec


def materialize(spec: BackendSpec, library=None) -> MaterializedBackend:
    """Pack ``spec`` against ``library`` (default library if None),
    LRU-cached so equal specs share one backend object; the key is the
    canonicalized spec, so specs differing only in fields their
    datapath ignores share one materialization."""
    spec = canonicalize(spec)
    if not spec.is_quantized:
        key = (0, spec)
        datapath = None
    else:
        datapath = get_datapath(spec.datapath_name)
        if datapath.needs_library:
            if library is None:
                from repro.core.library import get_default_library
                library = get_default_library()
            key = (_library_key(library), spec)
        else:
            key = (0, spec)
    hit = _CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        _CACHE.move_to_end(key)
        return hit
    _STATS["misses"] += 1
    consts = datapath.pack(spec, library) if datapath is not None else {}
    mb = MaterializedBackend(spec=spec, datapath=datapath, consts=consts,
                             canonical=True)
    _CACHE[key] = mb
    while len(_CACHE) > _CACHE_MAX:
        _CACHE.popitem(last=False)
    return mb


# ----------------------------------------------------------------------
# LutBank: the library axis as one device constant (DESIGN.md §2.4)
# ----------------------------------------------------------------------
@dataclass(frozen=True, eq=False)  # id-hash: cache guarantees uniqueness
class LutBank:
    """A stack of tile LUTs — the *multiplier axis* of a resilience
    sweep packed as one ``(n_mult, 256, 256)`` int32 device constant.

    Banks are what the batched resilience engine vmaps over: lane ``i``
    of a banked evaluation runs the model with ``luts[i]`` in every (or
    one) layer, bit-identical to materializing ``specs[i]`` and
    evaluating sequentially.  Build through ``bank_for`` to share banks
    across sweeps of the same (library, names, block_m) — the bank
    analogue of the per-spec materialization cache.

    Width-generic (DESIGN.md §2.6): lanes may MIX operand widths.  An
    8-bit lane's slice is its own product LUT; a composed wide lane's
    slice is its composition TILE's 256x256 LUT, with the lane's
    operand width recorded in ``bit_widths`` (the banked engines
    quantize and compose per lane from these).  All wide lanes of one
    bank must share a reduction tree (``reduce``) — the shift/add tree
    is compiled statically into the one banked program.
    """

    names: tuple[str, ...]
    luts: np.ndarray                  # (n_mult, 256, 256) int32 tiles
    block_m: int = 512
    bit_widths: Optional[tuple[int, ...]] = None   # None = all 8-bit
    reduce: str = "exact"
    #: Per-lane reduction trees (DESIGN.md §2.10).  ``None`` means every
    #: wide lane shares the static ``reduce`` (the historical contract
    #: the static-tree banked engines compile).  A tuple records each
    #: lane's own tree; only the ``fused`` variant can evaluate such a
    #: bank in one program (its kernel takes the tree as runtime data).
    reduces: Optional[tuple[str, ...]] = None

    def __post_init__(self):
        if self.luts.ndim != 3 or self.luts.shape[1:] != (256, 256):
            raise ValueError(
                f"LutBank wants (n, 256, 256) LUTs, got {self.luts.shape}"
                " — banked sweeps run on 256x256 tile LUTs (8-bit "
                "entries directly, composed wide entries via their "
                "tile; DESIGN.md §2.6)")
        if len(self.names) != self.luts.shape[0]:
            raise ValueError("one name per LUT slice required")
        if self.bit_widths is not None:
            if len(self.bit_widths) != len(self.names):
                raise ValueError("one bit width per lane required")
            from repro.approx.quant import TRACED_WIDTHS
            bad = sorted(set(self.bit_widths) - set(TRACED_WIDTHS))
            if bad:
                # the traced calibrate select would silently fall back
                # to its widest branch for any other width
                raise ValueError(
                    f"unsupported lane widths {bad}; banked engines "
                    f"run per-lane widths from {TRACED_WIDTHS}")
        if self.reduces is not None and len(self.reduces) != len(self.names):
            raise ValueError("one reduce per lane required")

    @property
    def n_mult(self) -> int:
        return len(self.names)

    @property
    def is_mixed_reduce(self) -> bool:
        """True when lanes carry more than one distinct reduction tree
        — only the runtime-tree ``fused`` engines can bank such a set."""
        if self.reduces is None:
            return False
        from repro.core.families import parse_reduce
        return len({parse_reduce(r) for r in self.reduces}) > 1

    @property
    def lane_reduce_codes(self) -> np.ndarray:
        """(n_mult, 2) int32 ``encode_reduce`` codes, one per lane (the
        runtime reduction selectors of the fused composed kernels;
        uniform banks repeat the shared ``reduce``)."""
        from repro.core.families import parse_reduce

        from .registry import encode_reduce
        rs = (self.reduces if self.reduces is not None
              else (self.reduce,) * self.n_mult)
        return np.asarray([encode_reduce(parse_reduce(r)) for r in rs],
                          dtype=np.int32)

    @property
    def lane_bits(self) -> np.ndarray:
        """(n_mult,) per-lane operand widths (int32)."""
        if self.bit_widths is None:
            return np.full(self.n_mult, 8, dtype=np.int32)
        return np.asarray(self.bit_widths, dtype=np.int32)

    @property
    def any_wide(self) -> bool:
        """True when any lane runs the composed (>8-bit) datapath —
        the static dispatch bit of the banked engines."""
        return bool((self.lane_bits > 8).any())

    @property
    def lane_masks(self) -> np.ndarray:
        """(n_mult,) uint32 per-lane 2W-bit product masks (0 marks a
        narrow lane — the banked engines' selector-and-truncation,
        matching the composed netlist's output width)."""
        from .registry import lane_mask_np
        return lane_mask_np(self.lane_bits)

    def spec(self, i: int, mode: str = "lut",
             variant: str = "ref") -> BackendSpec:
        """The serializable spec lane ``i`` of a banked sweep stands
        for (``bit_width``/``reduce_adder`` left to library inference,
        matching the specs sequential sweeps build)."""
        return BackendSpec(mode=mode, multiplier=self.names[i],
                           block_m=self.block_m, variant=variant)

    @staticmethod
    def from_library(names, library=None, block_m: int = 512,
                     mixed_reduce: bool = False) -> "LutBank":
        """Pack a (possibly mixed-width) candidate set: 8-bit entries
        contribute their own LUT, composed wide entries their tile's.
        By default raises when wide lanes disagree on the reduction
        tree (the static-tree banked engines compile ONE shift/add
        tree) — split such sweeps into one bank per reduction, or pass
        ``mixed_reduce=True`` to record per-lane trees for the runtime-
        tree ``fused`` engines (DESIGN.md §2.10)."""
        from repro.core.families import parse_reduce
        if library is None:
            from repro.core.library import get_default_library
            library = get_default_library()
        from repro.approx.quant import TRACED_WIDTHS
        names = tuple(names)
        luts, widths, reduces = [], [], {}
        for n in names:
            entry = library.entry(n)
            comp = library.composition_of(n)
            if entry.width not in TRACED_WIDTHS:
                raise ValueError(
                    f"bank lane {n!r} is {entry.width}-bit; banked "
                    f"sweeps support widths {TRACED_WIDTHS} (per-lane "
                    "width is selected at runtime from this set)")
            luts.append(np.asarray(library.tile_lut(n), dtype=np.int32))
            widths.append(int(entry.width))
            if comp is not None:
                reduces[n] = comp["reduce"]
        reduce = "exact"
        per_lane: Optional[tuple] = None
        if reduces:
            parsed = {parse_reduce(r) for r in reduces.values()}
            if len(parsed) > 1:
                if not mixed_reduce:
                    raise ValueError(
                        "mixed reduction trees in one bank: "
                        f"{sorted(set(reduces.values()))} — a banked "
                        "sweep compiles one static shift/add tree; "
                        "sweep each reduction family in its own bank, "
                        "or pass mixed_reduce=True to bank them "
                        "through the runtime-tree fused engines")
                per_lane = tuple(reduces.get(n, "exact") for n in names)
            else:
                reduce = next(iter(reduces.values()))
        return LutBank(names=names, luts=np.stack(luts), block_m=block_m,
                       bit_widths=tuple(widths), reduce=reduce,
                       reduces=per_lane)


# ----------------------------------------------------------------------
# PolicyBank: heterogeneous per-layer assignments over one LutBank
# (DESIGN.md §2.5)
# ----------------------------------------------------------------------
@dataclass(frozen=True, eq=False)  # id-hash: ndarray field
class PolicyBank:
    """K heterogeneous per-layer multiplier assignments sharing one
    ``LutBank`` — the *policy axis* of a heterogeneous sweep.

    ``assign[p, j]`` is the index into ``bank.names`` of the multiplier
    policy ``p`` uses in layer ``layers[j]``; layers not named here run
    the evaluation's base backend (golden int8 by default).  Row ``p``
    therefore stands for the serializable
    ``ApproxPolicy(default=base, overrides=spec_overrides(p))``, and
    ``repro.approx.layers.policy_bank_eval`` evaluates every row in one
    compiled program by gathering each layer's LUT lane
    ``luts[assign[:, j]]`` through the banked kernel — bit-identical to
    K sequential override evaluations.
    """

    bank: LutBank
    layers: tuple[str, ...]
    assign: np.ndarray                # (n_policies, n_layers) intp

    def __post_init__(self):
        a = np.asarray(self.assign, dtype=np.int32)
        if a.ndim != 2 or a.shape[1] != len(self.layers):
            raise ValueError(
                f"assign must be (n_policies, {len(self.layers)}), "
                f"got {a.shape}")
        if a.size and (a.min() < 0 or a.max() >= self.bank.n_mult):
            raise ValueError(
                f"assign indices must be in [0, {self.bank.n_mult}); "
                f"got range [{a.min()}, {a.max()}]")
        object.__setattr__(self, "assign", a)

    @property
    def n_policies(self) -> int:
        return int(self.assign.shape[0])

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def assignment(self, p: int) -> dict[str, str]:
        """Row ``p`` as a layer-name -> multiplier-name mapping."""
        return {layer: self.bank.names[self.assign[p, j]]
                for j, layer in enumerate(self.layers)}

    def spec_overrides(self, p: int, mode: str = "lut",
                       variant: str = "ref"
                       ) -> list[tuple[str, BackendSpec]]:
        """Serializable ``ApproxPolicy`` overrides for row ``p`` (layer
        order preserved; first-match-wins is irrelevant because layer
        names are exact, disjoint patterns)."""
        return [(layer, BackendSpec(mode=mode, multiplier=name,
                                    block_m=self.bank.block_m,
                                    variant=variant))
                for layer, name in self.assignment(p).items()]

    @staticmethod
    def from_assignments(assignments, library=None,
                         layers=None, block_m: int = 512,
                         fill: Optional[str] = None) -> "PolicyBank":
        """Pack layer->multiplier mappings into one shared bank.

        ``assignments`` is a sequence of dicts; ``layers`` defaults to
        the union of their keys in first-appearance order.  Every
        mapping must cover every layer (partial policies are expressed
        by leaving the layer out of ``layers``, not out of one row) —
        unless ``fill`` names a multiplier, in which case a row's
        unassigned layers run that multiplier.  ``fill="mul8u_exact"``
        keeps filled lanes bit-identical to the golden-int8 base the
        sequential evaluations default to (the exact 8-bit LUT computes
        the same products), which is how module-family assignments with
        disjoint layer coverage share one bank (DESIGN.md §2.12).  The
        distinct multiplier names are deduplicated into a single
        ``bank_for``-cached ``LutBank``.
        """
        assignments = list(assignments)
        if layers is None:
            layers = []
            for a in assignments:
                for name in a:
                    if name not in layers:
                        layers.append(name)
        layers = tuple(layers)
        names: list[str] = []
        rows: list[Mapping[str, str]] = []
        for a in assignments:
            missing = [l for l in layers if l not in a]
            if missing and fill is None:
                raise ValueError(
                    f"assignment {a!r} misses layers {missing} "
                    "(pass fill=<multiplier name> to pad partial rows)")
            row = dict(a) if not missing else {
                **{l: fill for l in missing}, **a}
            rows.append(row)
            for l in layers:
                if row[l] not in names:
                    names.append(row[l])
        bank = bank_for(names, library, block_m=block_m)
        index = {n: i for i, n in enumerate(bank.names)}
        assign = np.asarray([[index[r[l]] for l in layers]
                             for r in rows], dtype=np.int32)
        return PolicyBank(bank=bank, layers=layers, assign=assign)

    @staticmethod
    def uniform(names, layers, library=None,
                block_m: int = 512) -> "PolicyBank":
        """One row per multiplier name, assigned to every layer — the
        heterogeneous engine restricted to uniform policies (the
        equal-assignment consistency axis CI checks)."""
        names = list(names)
        return PolicyBank.from_assignments(
            [{l: n for l in layers} for n in names],
            library=library, layers=layers, block_m=block_m)

    @staticmethod
    def from_policies(policies, layers, library=None,
                      block_m: int = 512, mode: str = "lut"
                      ) -> "PolicyBank":
        """Bank assembly from *request* policies (DESIGN.md §2.8): each
        ``ApproxPolicy`` is resolved over ``layers`` via
        ``policy_assignment`` (fnmatch semantics, so uniform and
        partially-overridden policies both work), the distinct
        multiplier names deduplicate into one shared ``LutBank``, and
        row ``p`` of the result is policy ``p``'s per-layer lane
        assignment — the serve engine's request→lane mapping."""
        assignments = [policy_assignment(p, layers, mode=mode,
                                         block_m=block_m)
                       for p in policies]
        return PolicyBank.from_assignments(assignments, library=library,
                                           layers=tuple(layers),
                                           block_m=block_m)


def policy_assignment(policy, layers, *, mode: str = "lut",
                      block_m: int = 512) -> dict[str, str]:
    """Resolve an ``ApproxPolicy`` to a layer-tag → multiplier-name
    mapping over ``layers`` — the per-request half of serve-time bank
    assembly.  Every layer must resolve to a banked ``mode`` spec with
    the bank's ``block_m``; anything else (an f32 default, a lowrank
    override, a mismatched blocking) cannot ride a LUT-bank lane and
    raises with the offending layer named."""
    from .layers import spec_of   # runtime import: layers imports us
    out: dict[str, str] = {}
    for layer in layers:
        spec = spec_of(policy.backend_for(layer))
        if spec.mode != mode:
            raise ValueError(
                f"policy resolves layer {layer!r} to mode "
                f"{spec.mode!r}; mixed-policy serving batches every "
                f"request through the banked {mode!r} datapath — "
                f"express the request as a {mode!r}-mode policy "
                "(multiplier='mul8u_exact' emulates the exact product)")
        if spec.block_m != block_m:
            raise ValueError(
                f"policy resolves layer {layer!r} with block_m="
                f"{spec.block_m}, but the shared bank blocks at "
                f"{block_m} — one banked program compiles one blocking")
        out[layer] = spec.multiplier
    return out


_BANK_CACHE: "OrderedDict[tuple, LutBank]" = OrderedDict()
_BANK_CACHE_MAX = 16


def bank_for(names, library=None, block_m: int = 512,
             mixed_reduce: bool = False) -> LutBank:
    """LRU-cached ``LutBank.from_library``: repeated sweeps over the
    same candidate set (all-layers then per-layer, or explore() called
    twice) reuse one packed bank instead of restacking LUTs."""
    return packed_bank(names, library, block_m, mixed_reduce)[0]


def packed_bank(names, library=None, block_m: int = 512,
                mixed_reduce: bool = False) -> tuple[LutBank, bool]:
    """``bank_for``, and whether the bank was packed by this call
    (False: it came from the LRU cache)."""
    if library is None:
        from repro.core.library import get_default_library
        library = get_default_library()
    key = (_library_key(library), tuple(names), int(block_m),
           bool(mixed_reduce))
    hit = _BANK_CACHE.get(key)
    if hit is not None:
        _BANK_CACHE.move_to_end(key)
        return hit, False
    bank = LutBank.from_library(names, library, block_m=block_m,
                                mixed_reduce=mixed_reduce)
    _BANK_CACHE[key] = bank
    while len(_BANK_CACHE) > _BANK_CACHE_MAX:
        _BANK_CACHE.popitem(last=False)
    return bank, True


def materialize_cache_stats() -> dict:
    return {"hits": _STATS["hits"], "misses": _STATS["misses"],
            "size": len(_CACHE)}


def clear_materialize_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0
