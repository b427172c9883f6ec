"""Layer-level integration: injection policy + approx dense/conv.

``ApproxPolicy`` maps layer-name glob patterns to backends — the unit of
the paper's resilience analysis ("only one layer was modified and one
type of approximate multiplier was used in each experiment").  Models
route every projection through ``policy.matmul(name, x, w)`` and report
their multiplication counts per layer for the power model.

Policy entries may be ``BackendSpec``s (serializable names of a
configuration), the ``MaterializedBackend``s they cache to, or legacy
``MatmulBackend``s.  ``to_json``/``from_json`` round-trip the policy as
specs, so a chosen accelerator configuration ships inside checkpoints
and serve requests (DESIGN.md §2.2); ``materialize`` binds every entry
to a library once so jitted evals share traces.
"""
from __future__ import annotations

import fnmatch
import json
import re
import threading
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..tracing import span
from .backend import BackendLike, MatmulBackend, as_backend, backend_matmul
from .registry import get_datapath
from .specs import (BackendSpec, LutBank, MaterializedBackend, PolicyBank,
                    canonicalize)


def spec_of(backend: BackendLike) -> BackendSpec:
    """Best-effort serializable spec for any backend handle (legacy
    backends describe themselves via ``MatmulBackend.to_spec``)."""
    if backend is None:
        return BackendSpec()
    if isinstance(backend, BackendSpec):
        return backend
    if isinstance(backend, MaterializedBackend):
        return backend.spec
    if isinstance(backend, MatmulBackend):
        return backend.to_spec()
    raise TypeError(f"not a backend: {type(backend).__name__}")


@dataclass
class ApproxPolicy:
    """default backend + per-layer-pattern overrides (fnmatch globs,
    first match wins)."""
    default: BackendLike = field(default_factory=MatmulBackend)
    overrides: list[tuple[str, BackendLike]] = field(default_factory=list)

    def backend_for(self, name: str) -> BackendLike:
        for pat, be in self.overrides:
            if fnmatch.fnmatch(name, pat):
                return be
        return self.default

    def matmul(self, name: str, x: jax.Array, w: jax.Array) -> jax.Array:
        return backend_matmul(x, w, self.backend_for(name))

    def with_override(self, pattern: str, backend: BackendLike
                      ) -> "ApproxPolicy":
        return ApproxPolicy(default=self.default,
                            overrides=[(pattern, backend)] + list(self.overrides))

    # -- spec-first API -------------------------------------------------
    def materialize(self, library=None) -> "ApproxPolicy":
        """Bind every entry to ``library`` via the materialization cache
        so repeated evals of equal policies share backend objects (and
        therefore jit traces)."""
        def mat(be: BackendLike) -> MaterializedBackend:
            if isinstance(be, MaterializedBackend):
                return be
            if isinstance(be, MatmulBackend):
                # preserve hand-attached arrays instead of rebuilding
                # by multiplier name from the library
                return as_backend(be)
            return spec_of(be).materialize(library)
        return ApproxPolicy(
            default=mat(self.default),
            overrides=[(p, mat(be)) for p, be in self.overrides])

    def cache_key(self) -> tuple:
        """Hashable identity of this policy.  Spec-level (canonicalized
        per datapath) for spec/canonical entries; backends carrying
        hand-attached arrays (which a spec cannot describe) are salted
        with the backend object itself — id-hashed AND kept alive by
        the key, so a recycled id can never alias a stale cache hit."""
        def key_of(be: BackendLike):
            spec = canonicalize(spec_of(be))
            if isinstance(be, MaterializedBackend) and not be.canonical:
                return (spec, be)
            if isinstance(be, MatmulBackend) and (
                    be.lut is not None or be.factors_u is not None):
                return (spec, be)
            return spec
        return (key_of(self.default),
                tuple((p, key_of(be)) for p, be in self.overrides))

    # -- serialization --------------------------------------------------
    def to_json_dict(self) -> dict:
        def ser(be: BackendLike) -> dict:
            unfaithful = (
                (isinstance(be, MaterializedBackend) and not be.canonical)
                or (isinstance(be, MatmulBackend) and (
                    be.lut is not None or be.factors_u is not None)))
            if unfaithful:
                warnings.warn(
                    "serializing a backend with hand-attached arrays by "
                    "its spec; the arrays themselves are not captured — "
                    "deserialization rebuilds from the library by "
                    f"multiplier name ({spec_of(be).multiplier!r})",
                    UserWarning, stacklevel=3)
            return spec_of(be).to_dict()
        return {
            "default": ser(self.default),
            "overrides": [[p, ser(be)] for p, be in self.overrides],
        }

    def to_json(self) -> str:
        return json.dumps(self.to_json_dict(), sort_keys=True)

    @staticmethod
    def from_json_dict(d: dict) -> "ApproxPolicy":
        return ApproxPolicy(
            default=BackendSpec.from_dict(d["default"]),
            overrides=[(p, BackendSpec.from_dict(s))
                       for p, s in d.get("overrides", [])])

    @staticmethod
    def from_json(s: Union[str, dict]) -> "ApproxPolicy":
        if isinstance(s, str):
            s = json.loads(s)
        return ApproxPolicy.from_json_dict(s)


EXACT_POLICY = ApproxPolicy(default=MatmulBackend(mode="f32"))


# ----------------------------------------------------------------------
# Banked (vmapped) evaluation — the batched resilience engine's core
# (DESIGN.md §2.4)
# ----------------------------------------------------------------------
def _bank_lane_backend(lut: jax.Array, mode: str, variant: str,
                       block_m: int, reduce: Optional[str] = None,
                       mask=None, bits=None,
                       reduce_code=None) -> MaterializedBackend:
    """Backend for ONE vmap lane: a ``mode``-datapath backend whose LUT
    const is a traced ``(256, 256)`` slice of the bank (any datapath
    declaring ``bankable`` consumes ``consts['lut']`` this way).
    ``ste=False`` because banked evaluation is forward-only — routing
    around the custom_vjp wrapper keeps traced consts out of its
    non-differentiable spec argument (the forward math is identical
    either way).

    ``block_m`` is the bank's, and ``reduce`` the static reduction tree
    of a width-generic bank (``bank.any_wide``; None for an all-8-bit
    one).  Width-generic lanes additionally thread the lane's traced
    ``bits`` (quantization width) and 2W-bit product ``mask`` (0 =
    narrow lane), so one compiled program mixes 8-bit and composed
    12/16-bit lanes (DESIGN.md §2.6).  Under the ``fused`` variant the
    lane's traced ``reduce_code`` rides along too — the fused composed
    kernel takes the reduction tree as runtime data, which is what lets
    a mixed-reduce bank compile to one program (DESIGN.md §2.10)."""
    dp = get_datapath(mode if variant == "ref" else f"{mode}_{variant}")
    spec = BackendSpec(mode=mode, multiplier="<bank>",
                       block_m=block_m, ste=False, variant=variant)
    consts: dict = {"lut": lut, "block_m": block_m}
    if reduce is not None:
        from repro.core.families import parse_reduce
        consts.update(composed=True, bits=bits, mask=mask,
                      reduce=parse_reduce(reduce))
        if reduce_code is not None:
            consts["reduce_code"] = reduce_code
    return MaterializedBackend(spec=spec, datapath=dp, consts=consts)


def _wide_reduce(bank: LutBank) -> Optional[str]:
    """The static reduction tree a width-generic bank's lanes compile
    (None for an all-8-bit bank)."""
    return bank.reduce if bank.any_wide else None


def _check_bank_variant(bank: LutBank, variant: str) -> None:
    """A mixed-reduce bank encodes per-lane shift/add trees, which only
    the runtime-tree fused engines can select inside one program; the
    static-tree variants would silently run every lane under one tree."""
    if bank.is_mixed_reduce and variant != "fused":
        raise ValueError(
            f"bank mixes reduction trees ({sorted(set(bank.reduces))}); "
            f"the {variant!r} variant compiles one static tree — run "
            "mixed-reduce banks under variant='fused'")


def _lane_sharding(sharding):
    """1-D sharding for a wide bank's per-lane aux arrays, derived
    from the bank's (n, 256, 256) sharding (None when not derivable,
    e.g. a non-NamedSharding)."""
    from jax.sharding import NamedSharding
    if isinstance(sharding, NamedSharding):
        from repro.launch.mesh import lane_sharding
        return lane_sharding(sharding)
    return None


def bank_eval(fn, bank: LutBank, *, mode: str = "lut",
              variant: str = "ref",
              base: Optional[BackendLike] = None,
              layer_pattern: Optional[str] = None,
              sharding=None):
    """Evaluate ``fn(policy)`` for every multiplier in ``bank`` in ONE
    compiled program (``jit(vmap(...))`` over the bank axis).

    ``fn`` must be traceable (pure jax: arrays in, arrays out — no
    ``float()``/numpy on traced values).  ``mode``/``variant`` select
    the registered datapath the lanes run through (it must declare
    ``bankable``; see ``repro.approx.resilience.can_bank``).  Lane ``i``
    sees a policy whose swept entry emulates ``bank.names[i]``:

      * ``layer_pattern=None`` — the banked backend is the policy
        default (all-layers sweep, Table II);
      * ``layer_pattern='s1_b0_conv1'`` — only that layer is banked and
        the rest run ``base`` (per-layer sweep, Fig. 4; default golden
        int8).

    The bank axis threads through the model by vmap batching: layers
    before the first banked matmul stay unbatched (computed once and
    shared), everything downstream carries the lane axis.  Under the
    ``pallas`` variant the custom batching rule of
    ``repro.kernels.ops.approx_matmul_lut`` collapses the vmapped LUT
    into the banked kernel, one grid step per multiplier.

    ``sharding`` (an optional ``jax.sharding.Sharding`` for the
    ``(n_mult, 256, 256)`` bank) places lanes across devices; see
    ``repro.launch.mesh.bank_sharding``.  Returns ``fn``'s output
    stacked along a new leading ``n_mult`` axis.

    The program is kept for ``fn`` and its static signature while ``fn``
    lives (``bank_program``): a later bank of other multipliers, of the
    same signature, runs it again without a trace, lowering or load.

    The call is the span ``bank_eval.call`` (``repro.tracing``); the
    runtime's trace, lowering and load of the program nest inside it.
    Its ``reused`` is true when the program was kept from an earlier
    call.
    """
    program = "all" if layer_pattern is None else layer_pattern
    with span("bank_eval.call", program=program) as attrs:
        jitted, args, attrs["reused"] = _bank_program(
            fn, bank, mode=mode, variant=variant, base=base,
            layer_pattern=layer_pattern, sharding=sharding)
        return jitted(*args)


#: the banked programs of each traced function, by static signature
#: (``_bank_program``); an entry goes when its function is freed
_PROGRAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_PROGRAMS_LOCK = threading.Lock()


def bank_program(fn, bank: LutBank, *, mode: str = "lut",
                 variant: str = "ref",
                 base: Optional[BackendLike] = None,
                 layer_pattern: Optional[str] = None,
                 sharding=None):
    """The program ``bank_eval`` runs, and its arguments:
    ``(jitted, args)``, where ``jitted(*args)`` is ``bank_eval``'s
    result.

    The bank enters the program only as arguments: its LUTs, and a
    width-generic bank's per-lane bits, masks and reduce codes.  All
    else the trace reads is the static signature: ``fn``, ``mode``,
    ``variant``, ``layer_pattern``, ``base`` (the object),
    ``bank.block_m`` and, for a width-generic bank, ``bank.reduce``.
    One ``jitted`` is kept per ``fn`` and signature for as long as
    ``fn`` lives, so a later bank of the same signature gets the same
    object and JAX's cache of it skips the trace, lowering and load (a
    bank of another lane count or sharding is traced again in it).
    ``fn`` is held weakly: keep it alive while calling ``jitted``.

    The program is named ``bank_all`` (``layer_pattern=None``) or
    ``bank_<layer>``, so a compiled module reads ``jit_bank_all``.  Its
    body is the span ``bank_eval.trace``, which runs only while JAX
    traces the program: one span per trace."""
    return _bank_program(fn, bank, mode=mode, variant=variant, base=base,
                         layer_pattern=layer_pattern,
                         sharding=sharding)[:2]


def _bank_program(fn, bank: LutBank, *, mode: str, variant: str,
                  base: Optional[BackendLike],
                  layer_pattern: Optional[str], sharding):
    """``bank_program``'s ``(jitted, args)``, and whether ``jitted`` was
    kept from an earlier call."""
    _check_bank_variant(bank, variant)
    if layer_pattern is None:
        base = None                     # the all-layers policy reads none
    elif base is None:
        base = BackendSpec.golden().materialize()
    reduce = _wide_reduce(bank)
    # ``base`` by identity; the entry holds it, so its id stays its own
    key = (mode, variant, layer_pattern, id(base), bank.block_m, reduce)
    with _PROGRAMS_LOCK:
        try:
            programs, fn_ref = _PROGRAMS.setdefault(fn, {}), weakref.ref(fn)
        except TypeError:       # unhashable or not weakly referable
            programs, fn_ref = {}, lambda: fn
        entry = programs.get(key)
        reused = entry is not None
        if not reused:
            entry = programs[key] = (_new_bank_program(
                fn_ref, mode, variant, base, layer_pattern, bank.block_m,
                reduce), base)
    jitted = entry[0]

    luts = jnp.asarray(bank.luts)
    if sharding is not None:
        luts = jax.device_put(luts, sharding)
    if reduce is None:
        return jitted, (luts,), reused
    # mixed-width bank: per-lane quantization width + product mask
    # (selector + 2W-bit truncation) and reduce code ride the vmapped
    # axis (DESIGN.md §2.6, §2.10)
    aux = (jnp.asarray(bank.lane_bits, jnp.int32),
           jnp.asarray(bank.lane_masks, jnp.uint32),
           jnp.asarray(bank.lane_reduce_codes, jnp.int32))
    aux_sharding = None if sharding is None else _lane_sharding(sharding)
    if aux_sharding is not None:
        aux = tuple(jax.device_put(a, aux_sharding) for a in aux)
    return jitted, (luts, *aux), reused


def _new_bank_program(fn_ref, mode: str, variant: str, base,
                      layer_pattern: Optional[str], block_m: int,
                      reduce: Optional[str]):
    """``jit(vmap(lane))`` for one static signature.  The lane reads
    only these values and its arguments, never a ``LutBank``, so the
    program serves every bank of the signature and keeps none alive."""
    program = "all" if layer_pattern is None else layer_pattern

    def lane(lut, *wide):
        with span("bank_eval.trace", program=program):
            bits, mask, code = wide or (None, None, None)
            mb = _bank_lane_backend(lut, mode, variant, block_m, reduce,
                                    mask=mask, bits=bits, reduce_code=code)
            policy = (ApproxPolicy(default=mb) if layer_pattern is None
                      else ApproxPolicy(default=base,
                                        overrides=[(layer_pattern, mb)]))
            fn = fn_ref()
            if fn is None:
                raise ReferenceError(
                    "the traced function of this banked program was "
                    "freed; keep it alive while calling the program")
            return fn(policy)

    lane.__name__ = "bank_" + re.sub(r"\W", "_", program)
    return jax.jit(jax.vmap(lane))


def bank_assignment_overrides(bank: LutBank, luts, assign_row, layers,
                              *, mode: str = "lut", variant: str = "ref",
                              lane_bits=None, lane_masks=None,
                              lane_codes=None
                              ) -> list[tuple[str, MaterializedBackend]]:
    """Traced per-layer policy overrides for ONE lane of a banked
    program: layer ``layers[j]`` runs a backend whose LUT const is the
    gathered slice ``luts[assign_row[j]]``.  ``luts`` / ``assign_row``
    (and, for width-generic banks, ``lane_bits`` / ``lane_masks``) are
    traced arrays; ``bank`` supplies only static metadata (block_m,
    any_wide, reduce).  Shared by ``policy_bank_eval`` (one vmap lane
    per candidate policy) and the continuous-batching serve engine
    (one vmap lane per request slot) — both get O(1) compiled programs
    regardless of how many distinct assignments are in flight."""
    overrides = []
    for j, layer in enumerate(layers):
        lut = jnp.take(luts, assign_row[j], axis=0)       # (256,256)
        if bank.any_wide:
            # width-generic: each layer gathers its multiplier's
            # quantization width + product mask (and, for the fused
            # variant, reduce code) alongside the tile LUT
            # (DESIGN.md §2.6, §2.10)
            mb = _bank_lane_backend(
                lut, mode, variant, bank.block_m, _wide_reduce(bank),
                mask=jnp.take(lane_masks, assign_row[j]),
                bits=jnp.take(lane_bits, assign_row[j]),
                reduce_code=(None if lane_codes is None else
                             jnp.take(lane_codes, assign_row[j], axis=0)))
        else:
            mb = _bank_lane_backend(lut, mode, variant, bank.block_m)
        overrides.append((layer, mb))
    return overrides


def policy_for_lane(pbank: PolicyBank, p: int, *, mode: str = "lut",
                    variant: str = "ref",
                    base: Optional[BackendLike] = None) -> ApproxPolicy:
    """The sequential (serializable) policy lane ``p`` of a
    ``policy_bank_eval`` stands for: ``base`` (golden int8 by default)
    everywhere, with layer ``j`` overridden to multiplier
    ``pbank.bank.names[pbank.assign[p, j]]``.  Evaluating this policy
    sequentially is bit-identical to lane ``p`` of the banked program —
    the contract tests and benchmarks assert."""
    base = base if base is not None else BackendSpec.golden().materialize()
    return ApproxPolicy(default=base,
                        overrides=pbank.spec_overrides(p, mode=mode,
                                                       variant=variant))


def policy_bank_eval(fn, pbank: PolicyBank, *, mode: str = "lut",
                     variant: str = "ref",
                     base: Optional[BackendLike] = None,
                     sharding=None, assign_sharding=None):
    """Evaluate ``fn(policy)`` for every *heterogeneous* assignment row
    of ``pbank`` in ONE compiled program (``jit(vmap(...))`` over the
    policy axis) — the per-layer generalization of ``bank_eval``.

    Where ``bank_eval`` lane ``i`` runs ONE multiplier in the swept
    entry, ``policy_bank_eval`` lane ``p`` composes a different
    multiplier per named layer: layer ``j`` gathers its own LUT lane
    ``luts[assign[p, j]]`` from the shared bank, so K heterogeneous
    policies over D distinct multipliers cost one program and D LUTs of
    device memory regardless of K.  Layers not named in ``pbank.layers``
    run ``base`` (default golden int8) unbatched.

    ``fn`` must be traceable (see ``bank_eval``); ``mode``/``variant``
    select the registered datapath, which must declare ``bankable``
    (under the ``pallas`` variant the custom batching rule of
    ``repro.kernels.ops.approx_matmul_lut`` collapses each layer's
    gathered LUT lanes into the banked kernel).  ``sharding``
    optionally places the ``(n_mult, 256, 256)`` bank, and
    ``assign_sharding`` the ``(n_policies, n_layers)`` assignment
    matrix (``repro.launch.mesh.policy_sharding``) — sharding the
    assignment's leading axis makes XLA partition the whole vmapped
    program per policy lane.

    Returns ``fn``'s output stacked along a new leading ``n_policies``
    axis, bit-identical per lane to the sequential evaluation of
    ``policy_for_lane(pbank, p)``.
    """
    luts = jnp.asarray(pbank.bank.luts)
    if sharding is not None:
        luts = jax.device_put(luts, sharding)
    assign = jnp.asarray(pbank.assign, dtype=jnp.int32)
    if assign_sharding is not None:
        assign = jax.device_put(assign, assign_sharding)
    if base is None:
        base = BackendSpec.golden().materialize()
    _check_bank_variant(pbank.bank, variant)
    any_wide = pbank.bank.any_wide
    bank_bits = jnp.asarray(pbank.bank.lane_bits, jnp.int32)
    bank_masks = jnp.asarray(pbank.bank.lane_masks, jnp.uint32)
    bank_codes = jnp.asarray(pbank.bank.lane_reduce_codes, jnp.int32)

    def lane(assign_row):
        overrides = bank_assignment_overrides(
            pbank.bank, luts, assign_row, pbank.layers,
            mode=mode, variant=variant,
            lane_bits=bank_bits if any_wide else None,
            lane_masks=bank_masks if any_wide else None,
            lane_codes=bank_codes if any_wide else None)
        policy = ApproxPolicy(default=base, overrides=overrides)
        return fn(policy)

    return jax.jit(jax.vmap(lane))(assign)


def dense(policy: ApproxPolicy, name: str, x: jax.Array, w: jax.Array,
          b: Optional[jax.Array] = None) -> jax.Array:
    y = policy.matmul(name, x, w)
    if b is not None:
        y = y + b
    return y


def conv2d(policy: ApproxPolicy, name: str, x: jax.Array, w: jax.Array,
           stride: int = 1, padding: str = "SAME",
           b: Optional[jax.Array] = None) -> jax.Array:
    """NHWC conv via im2col + backend matmul, so the multiplier
    emulation covers convolutions exactly as TFApprox's AxConv2D does.

    x: (B,H,W,Cin), w: (kh,kw,Cin,Cout).
    """
    kh, kw, cin, cout = w.shape
    bsz, h, wd, _ = x.shape
    ho = conv_output_size(h, kh, stride, padding)
    wo = conv_output_size(wd, kw, stride, padding)
    # patches by strided slices: exact copies of the activations.  (As a
    # convolution with a 0/1 kernel a TPU would run them in bf16 unless
    # asked for HIGHEST precision, which costs most of the compile time
    # of a banked all-layers program.)
    pads = jax.lax.padtype_to_pads((h, wd), (kh, kw), (stride, stride),
                                   padding)
    xp = jnp.pad(x, ((0, 0), *pads, (0, 0)))
    patches = jnp.stack(
        [xp[:, i:i + (ho - 1) * stride + 1:stride,
            j:j + (wo - 1) * stride + 1:stride, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    # features ordered (cin, kh, kw); reorder w to match
    feat = cin * kh * kw
    w2d = jnp.transpose(w, (2, 0, 1, 3)).reshape(feat, cout)
    y = policy.matmul(name, patches.reshape(-1, feat), w2d)
    y = y.reshape(bsz, ho, wo, cout)
    if b is not None:
        y = y + b
    return y


def conv_output_size(size: int, kernel: int, stride: int,
                     padding: str) -> int:
    """Spatial output size matching ``jax.lax`` conv semantics."""
    if padding == "SAME":
        return -(-size // stride)                 # ceil(size / stride)
    if padding == "VALID":
        if size < kernel:
            return 0
        return (size - kernel) // stride + 1
    raise ValueError(f"unsupported padding {padding!r}")


def conv_mult_count(x_shape, w_shape, stride: int = 1,
                    padding: str = "SAME") -> int:
    """Number of scalar multiplications in this conv (power model),
    for the output dims ``conv2d`` actually produces."""
    bsz, h, w_, cin = x_shape
    kh, kw, _, cout = w_shape
    ho = conv_output_size(h, kh, stride, padding)
    wo = conv_output_size(w_, kw, stride, padding)
    return bsz * ho * wo * kh * kw * cin * cout


def dense_mult_count(x_shape, w_shape) -> int:
    m = 1
    for d in x_shape[:-1]:
        m *= d
    k, n = w_shape
    return m * k * n
