"""Matmul backends: the accelerator datapath being emulated.

Every projection matmul in every model flows through ``backend_matmul``.
Modes (each a registered datapath, see ``repro.approx.registry``):

  * ``f32`` / ``bf16`` — exact float (the paper's pre-quantization net)
  * ``int8``           — exact uint8-quantized datapath (the paper's
                         "golden" 8-bit multiplier)
  * ``lut``            — approximate multiplier, bit-true 256x256 LUT
                         emulation (TFApprox port; paper-faithful)
  * ``lowrank``        — approximate multiplier, rank-R factored LUT:
                         R 256-entry table lookups + R MXU matmuls
                         (TPU-native adaptation, DESIGN.md §4.2)

The preferred handle is a ``repro.approx.specs.BackendSpec`` (or the
``MaterializedBackend`` it caches to); the legacy ndarray-carrying
``MatmulBackend`` remains as a deprecation shim and is converted on
entry.  Datapath selection goes through the registry — there is no
mode if/elif chain here, so new datapaths plug in without editing this
module (DESIGN.md §2).

Gradients: straight-through estimator — backward pass is the exact f32
matmul VJP, enabling beyond-paper approximate-aware training (the paper
itself performs no retraining).

int32 accumulation of raw uint8 code products is bit-safe for
K < 2^31 / 255^2 = 33 030, which covers every assigned architecture
(max contraction dim = 24 576, nemotron-4-15b d_ff).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .quant import QuantParams, calibrate, quantize
from .registry import MAX_LUT_K, get_datapath
from .specs import BackendSpec, MaterializedBackend, materialize


# ----------------------------------------------------------------------
# Legacy shim (pre-spec API): id-hashed dataclass carrying raw arrays.
# Prefer BackendSpec everywhere new; this stays so existing call sites
# and tests keep working unchanged.
# ----------------------------------------------------------------------
@dataclass(frozen=True, eq=False)  # eq=False: id-hash (ndarray fields)
class MatmulBackend:
    mode: str = "bf16"                       # f32|bf16|int8|lut|lowrank
    multiplier: str = "mul8u_exact"          # library entry name
    lut: Optional[np.ndarray] = None         # (256,256) int32 product LUT
    factors_u: Optional[np.ndarray] = None   # (R,256) f32
    factors_v: Optional[np.ndarray] = None   # (R,256) f32
    rank: int = 0
    block_m: int = 512                       # LUT-emulation row blocking
    ste: bool = True                         # straight-through gradients
    use_pallas: bool = False                 # route through Pallas kernels

    @staticmethod
    def exact(mode: str = "bf16") -> "MatmulBackend":
        return MatmulBackend(mode=mode)

    @staticmethod
    def from_library(
        name: str,
        mode: str = "lut",
        rank: Optional[int] = None,
        library=None,
        use_pallas: bool = False,
    ) -> "MatmulBackend":
        """Deprecated: use ``BackendSpec.from_library(...).materialize()``.
        Builds a legacy backend emulating library multiplier ``name``."""
        warnings.warn(
            "MatmulBackend.from_library is deprecated; use "
            "BackendSpec.from_library(name, ...).materialize(library)",
            DeprecationWarning, stacklevel=2)
        from repro.core.library import get_default_library
        from .registry import pack_lowrank, pack_lut
        lib = library if library is not None else get_default_library()
        spec = BackendSpec(mode=mode, multiplier=name, rank=rank,
                           variant="pallas" if use_pallas else "ref")
        lut = pack_lut(spec, lib)["lut"]
        lr = pack_lowrank(spec, lib)     # shares the auto-rank heuristic
        return MatmulBackend(
            mode=mode, multiplier=name, lut=lut,
            factors_u=lr["u"], factors_v=lr["v"],
            rank=int(lr["u"].shape[0]), use_pallas=use_pallas,
        )

    def to_spec(self) -> BackendSpec:
        """Best-effort serializable spec: faithful whenever the arrays
        came from a library (every non-test call site); the single
        source of truth for the legacy-field -> spec mapping."""
        return BackendSpec(
            mode=self.mode, multiplier=self.multiplier,
            rank=(int(self.rank) or None), block_m=self.block_m,
            ste=self.ste,
            variant="pallas" if self.use_pallas else "ref")


BackendLike = Union[None, BackendSpec, MaterializedBackend, MatmulBackend]


def as_backend(backend: BackendLike) -> MaterializedBackend:
    """Coerce any accepted backend handle to a MaterializedBackend."""
    if backend is None:
        return materialize(BackendSpec())
    if isinstance(backend, MaterializedBackend):
        return backend
    if isinstance(backend, BackendSpec):
        return materialize(backend)
    if isinstance(backend, MatmulBackend):
        return _from_legacy(backend)
    raise TypeError(f"not a backend: {type(backend).__name__}")


def _from_legacy(be: MatmulBackend) -> MaterializedBackend:
    spec = be.to_spec()
    if not spec.is_quantized:
        return materialize(spec)
    dp = get_datapath(spec.datapath_name)
    if not dp.needs_library:                 # int8: no consts to carry
        return materialize(spec)
    # Raw arrays were attached by hand — wrap them uncached (id-hash
    # semantics identical to the legacy class).
    consts: dict = {}
    if be.mode.startswith("lut"):
        if be.lut is None:
            raise ValueError("legacy lut backend without a LUT")
        consts = {"lut": np.asarray(be.lut, np.int32),
                  "block_m": int(be.block_m)}
    elif be.mode.startswith("lowrank"):
        if be.factors_u is None or be.factors_v is None:
            raise ValueError("legacy lowrank backend without factors")
        consts = {"u": np.asarray(be.factors_u, np.float32),
                  "v": np.asarray(be.factors_v, np.float32)}
    else:
        raise ValueError(f"legacy backend mode {be.mode!r} needs a spec")
    return MaterializedBackend(spec=spec, datapath=dp, consts=consts)


# ----------------------------------------------------------------------
# Quantized execution (operates on uint8 codes stored as int32)
# ----------------------------------------------------------------------
def _quantized_matmul(x2d: jax.Array, w: jax.Array,
                      backend: MaterializedBackend) -> jax.Array:
    dp = backend.datapath
    if getattr(dp, "fused", False):
        # single-program datapath (DESIGN.md §2.10): calibration,
        # quantization, gather, accumulation and dequant all live in
        # the datapath's one fused kernel — hand it the float operands.
        return dp.forward_fused(x2d, w, backend.consts)
    # operand width of the emulated datapath (8 for the paper's
    # baseline; 12/16 for composed wide entries, DESIGN.md §2.6).  May
    # be a traced per-lane scalar inside a mixed-width banked eval.
    bits = backend.consts.get("bits", 8)
    # The datapath's integer result is exact on every backend.  Fencing
    # it (codes in, accumulator out) keeps the float code on both sides
    # compiled alike whichever datapath fills the fence: on a TPU, XLA
    # otherwise fuses quantize/dequant into the datapath's own ops
    # differently per datapath, and the rounding of what follows drifts.
    # The name scopes ``quantize`` and ``dequant`` mark the float code
    # on each side in the compiled program.
    with jax.named_scope("quantize"):
        qp_a = calibrate(x2d, bits=bits)
        qp_w = calibrate(w, bits=bits)
        qa, qw = jax.lax.optimization_barrier(
            (quantize(x2d, qp_a), quantize(w, qp_w)))
    s = jax.lax.optimization_barrier(dp.forward_q(qa, qw, backend.consts))
    with jax.named_scope("dequant"):
        za, zw = qp_a.zero_point, qp_w.zero_point
        k = x2d.shape[1]
        if dp.exact_int32:
            # exact datapath: Σ (qa-za)(qw-zw) with int32 accumulation
            row = jnp.sum(qa, axis=1, dtype=jnp.int32)        # (M,)
            col = jnp.sum(qw, axis=0, dtype=jnp.int32)        # (N,)
            acc = (s - zw * row[:, None] - za * col[None, :]
                   + k * za * zw).astype(jnp.float32)
        else:
            s = s.astype(jnp.float32)
            row = jnp.sum(qa, axis=1, dtype=jnp.int32).astype(jnp.float32)
            col = jnp.sum(qw, axis=0, dtype=jnp.int32).astype(jnp.float32)
            zaf, zwf = za.astype(jnp.float32), zw.astype(jnp.float32)
            # trunc is an exact identity on these integer-valued
            # products but pins each one to its own f32 rounding, so
            # XLA/LLVM cannot contract mul+sub into a single-rounding
            # FMA — without it the result depends on the surrounding
            # compilation context and the variants stop being
            # bit-identical (see kernels/fused_matmul ``_dequant`` for
            # the full rationale).
            t_row = jnp.trunc(zwf * row[:, None])
            t_col = jnp.trunc(zaf * col[None, :])
            t_k = jnp.trunc(k * zaf * zwf)
            acc = s - t_row - t_col + t_k
        return acc * (qp_a.scale * qp_w.scale)


# ----------------------------------------------------------------------
# Public entry point with STE gradients
# ----------------------------------------------------------------------
def _forward_2d(x2d: jax.Array, w: jax.Array,
                backend: MaterializedBackend) -> jax.Array:
    if backend.mode == "f32":
        return jnp.dot(x2d, w, preferred_element_type=jnp.float32)
    if backend.mode == "bf16":
        return jnp.dot(x2d.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return _quantized_matmul(x2d.astype(jnp.float32),
                             w.astype(jnp.float32), backend)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ste_matmul(x2d, w, backend):
    return _forward_2d(x2d, w, backend)


def _ste_fwd(x2d, w, backend):
    return _forward_2d(x2d, w, backend), (x2d, w)


def _ste_bwd(backend, res, g):
    x2d, w = res
    g = g.astype(jnp.float32)
    dx = jnp.dot(g, w.T.astype(jnp.float32)).astype(x2d.dtype)
    dw = jnp.dot(x2d.T.astype(jnp.float32), g).astype(w.dtype)
    return dx, dw


_ste_matmul.defvjp(_ste_fwd, _ste_bwd)


# ----------------------------------------------------------------------
# Prepared weights (beyond-paper serving optimization, EXPERIMENTS §Perf)
# ----------------------------------------------------------------------
# The weight-side rank tables V_r(q_w) are STATIC per checkpoint: a real
# deployment precomputes them offline.  ``prepare_weight`` replaces a
# projection weight leaf with {tabs: (R,K,N) bf16, colsum, scales},
# turning per-step work into R plain matmuls — no weight requantization,
# no f32 table gather, 2 bytes/element instead of 4.
def prepare_weight(w, backend: BackendLike) -> dict:
    mb = as_backend(backend)
    w = jnp.asarray(w, jnp.float32)
    qp_w = calibrate(w)
    qw = quantize(w, qp_w)
    v = jnp.asarray(mb.consts["v"])               # (R,256)
    tabs = jnp.take(v, qw, axis=1).astype(jnp.bfloat16)   # (R,K,N)
    colsum = jnp.sum(qw, axis=0, dtype=jnp.int32).astype(jnp.float32)
    return {
        "tabs": tabs,
        "colsum": colsum,
        "w_scale": qp_w.scale,
        "w_zp": qp_w.zero_point.astype(jnp.float32),
    }


def is_prepared(w) -> bool:
    return isinstance(w, dict) and "tabs" in w


def _prepared_matmul(x2d: jax.Array, pw: dict,
                     backend: MaterializedBackend) -> jax.Array:
    qp_a = calibrate(x2d)
    qa = quantize(x2d, qp_a)
    u = jnp.asarray(backend.consts["u"])          # (R,256)
    ua = jnp.take(u, qa, axis=1).astype(jnp.bfloat16)     # (R,M,K)
    y_q = jax.lax.dot_general(
        ua, pw["tabs"], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).sum(axis=0)   # (M,N)
    k = x2d.shape[1]
    row = jnp.sum(qa, axis=1, dtype=jnp.int32).astype(jnp.float32)
    zaf = qp_a.zero_point.astype(jnp.float32)
    acc = (y_q - pw["w_zp"] * row[:, None] - zaf * pw["colsum"][None, :]
           + k * zaf * pw["w_zp"])
    return acc * (qp_a.scale * pw["w_scale"])


_PROJECTION_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "wi", "wg", "in_proj", "out_proj",
    "wuq", "wdq", "wqr", "wdkv", "wuk", "wuv", "wkr", "img_proj",
})


def prepare_tree(params, backend: BackendLike):
    """Pre-pack every projection weight in a param pytree for lowrank
    serving (DESIGN.md §4.2, §Perf).  Handles stacked leading dims
    (scan groups, experts) by vmapping ``prepare_weight``."""
    mb = as_backend(backend)

    def pack(v):
        fn = prepare_weight
        for _ in range(v.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, None))
        return fn(v, mb)

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if (k in _PROJECTION_LEAVES and hasattr(v, "ndim")
                        and v.ndim >= 2):
                    out[k] = pack(v)
                else:
                    out[k] = walk(v)
            return out
        return node

    return walk(params)


def backend_matmul(x: jax.Array, w, backend: BackendLike = None
                   ) -> jax.Array:
    """x: (..., K) @ w: (K, N) -> (..., N) f32 through the selected
    accelerator datapath.  ``backend`` may be a BackendSpec, a
    MaterializedBackend, a legacy MatmulBackend or None (bf16);
    ``w`` may be a prepared-weight dict."""
    mb = as_backend(backend)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)
    if is_prepared(w):
        y = _prepared_matmul(x2d.astype(jnp.float32), w, mb)
        return y.reshape(*lead, y.shape[-1])
    if not mb.spec.is_quantized or not mb.ste:
        y = _forward_2d(x2d, w, mb)
    else:
        y = _ste_matmul(x2d, w, mb)
    return y.reshape(*lead, w.shape[-1])
