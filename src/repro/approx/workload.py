"""Workload layer: model + eval data + NAMED quality metrics
(DESIGN.md §2.7).

A ``Workload`` bundles everything the DSE needs to measure application-
level quality under an ``ApproxPolicy``, in both calling conventions
the sweeps understand (subsuming the older scalar ``BankableEval``):

  * ``fn(policy) -> {metric: float}`` — the sequential closure (free to
    jit internally, call numpy, return Python floats), and
  * ``traceable_metrics(policy) -> {metric: jax scalar}`` — its
    pure-jax core, which the batched engines wrap in ``jit(vmap(...))``
    over a multiplier bank (DESIGN.md §2.4).

Metric names are registered as ``workload``-provenance axes in
``repro.approx.objectives`` at construction, each with a direction, so
``explore(workload=..., objectives=(...))`` can Pareto over any mix of
quality metrics and library cost axes.  ``primary`` names the metric
legacy scalar call sites read: ``workload(policy)`` returns
``float(fn(policy)[primary])`` and the scalar-only ``.traceable``
property projects the traceable core the same way, so a ``Workload``
drops into every ``eval_fn=``-shaped call site unchanged.

Shipped adapters (built on ``repro.models``):

  * ``classification(cfg, params)`` — ResNet / synthetic-CIFAR top-1
    accuracy, the paper's case study (the historical behavior);
  * ``logit_fidelity(forward, inputs)`` — generic logit-MAE + top-1
    agreement vs the f32 model (the continuous quality axis where
    datapath width shows; DESIGN.md §2.6);
  * ``lm_fidelity(cfg)`` / ``lm_perplexity(cfg)`` — the same fidelity
    metrics, and loss/perplexity, for any registered decoder-family LM
    config (``repro.configs.get_config``/``repro.models.registry``),
    so resilience analysis and DSE run over LM scenarios, not just
    ResNet.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, Mapping, Optional, Sequence, Union

from .layers import ApproxPolicy, EXACT_POLICY
from .objectives import ensure_objective

MetricFn = Callable[[ApproxPolicy], Mapping[str, Any]]


@dataclass
class Workload:
    """A named evaluation scenario: policy in, metric dict out.

    ``metrics`` fixes the metric names (and their order in sweep rows);
    ``directions`` maps each to "max"/"min" (default "max") and is
    registered into the objectives registry at construction.
    ``layer_counts`` optionally carries the model's per-layer
    multiplication counts so ``explore(workload=...)`` needs no second
    argument.  ``traceable_metrics`` may be ``None`` — the workload
    then runs on the sequential sweep paths only (``batch=True``
    requests fall back, exactly like a plain-callable eval)."""

    name: str
    fn: MetricFn
    metrics: tuple[str, ...]
    primary: Optional[str] = None
    traceable_metrics: Optional[MetricFn] = None
    directions: Mapping[str, str] = field(default_factory=dict)
    layer_counts: Optional[dict[str, int]] = None

    def __post_init__(self):
        if not self.metrics:
            raise ValueError("a Workload needs at least one metric")
        if self.primary is None:
            self.primary = self.metrics[0]
        if self.primary not in self.metrics:
            raise ValueError(f"primary {self.primary!r} not among "
                             f"metrics {self.metrics}")
        for m in self.metrics:
            ensure_objective(m, self.directions.get(m, "max"),
                             source="workload")

    # -- calling conventions -------------------------------------------
    def measure(self, policy: ApproxPolicy) -> dict[str, float]:
        """Sequential evaluation: every metric as a Python float, in
        ``metrics`` order."""
        out = self.fn(policy)
        return {m: float(out[m]) for m in self.metrics}

    def __call__(self, policy: ApproxPolicy) -> float:
        """Legacy scalar convention: the primary metric's value."""
        return float(self.fn(policy)[self.primary])

    @property
    def primary_direction(self) -> str:
        return self.directions.get(self.primary, "max")

    @property
    def traceable(self):
        """Scalar-primary projection of the traceable core — the shape
        ``bank_eval``/``policy_bank_eval`` call sites and ``can_bank``
        historically expect (None when the workload has no traceable
        core; unused metric computations are dead-code-eliminated by
        XLA)."""
        if self.traceable_metrics is None:
            return None
        tm, primary = self.traceable_metrics, self.primary
        return lambda policy: tm(policy)[primary]

    def cached(self, cache: dict) -> "Workload":
        """The same workload through a policy-keyed metric-dict cache
        (the ``explore()`` resume/widen mechanism)."""
        def fn(policy: ApproxPolicy) -> dict[str, float]:
            key = policy.cache_key()
            if key not in cache:
                cache[key] = self.measure(policy)
            return cache[key]
        return replace(self, fn=fn)


def as_workload(eval_fn) -> Workload:
    """Normalize any sweep evaluation handle into a ``Workload``:

      * a ``Workload`` passes through unchanged;
      * a ``BankableEval`` (anything with ``fn`` + ``traceable``
        attributes) becomes a single-metric ``accuracy`` workload whose
        traceable core is preserved for the batched engines;
      * a plain callable becomes a sequential-only ``accuracy``
        workload.

    This is the shim that keeps every pre-§2.7 ``eval_fn(policy) ->
    float`` call site working across the sweeps and the DSE facade."""
    if isinstance(eval_fn, Workload):
        return eval_fn
    traceable = getattr(eval_fn, "traceable", None)
    seq = getattr(eval_fn, "fn", eval_fn)
    if not callable(seq):
        raise TypeError(f"not an evaluation function: {eval_fn!r}")
    return Workload(
        name=getattr(eval_fn, "name", None)
        or getattr(eval_fn, "__name__", type(eval_fn).__name__),
        fn=lambda policy: {"accuracy": seq(policy)},
        metrics=("accuracy",),
        traceable_metrics=(None if traceable is None else
                           (lambda policy: {"accuracy": traceable(policy)})),
        directions={"accuracy": "max"})


# ----------------------------------------------------------------------
# Shipped adapters
# ----------------------------------------------------------------------
def classification(cfg, params, *, eval_n: int = 256, batch: int = 64,
                   name: Optional[str] = None,
                   fidelity: bool = False) -> Workload:
    """ResNet / synthetic-CIFAR top-1 accuracy — the paper's case-study
    quality metric, as a bank-traceable workload (drop-in for the
    historical ``BankableEval`` the resilience benchmarks built by
    hand).

    ``fidelity=True`` adds ``logit_mae`` (minimize, PRIMARY) against
    the golden-int8 reference logits: the continuous quality axis the
    surrogate predict stage trains and gates on (DESIGN.md §2.11) —
    top-1 accuracy quantizes to 1/eval_n steps, which starves rank
    statistics of resolution while logit MAE keeps moving.  Accuracy
    stays measured on every point either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import CifarBatches
    from repro.models import resnet

    data = CifarBatches("test", eval_n, batch)
    eval_batches = list(data.eval_batches())
    images = jnp.asarray(np.stack([b["images"] for b in eval_batches]))
    labels = jnp.asarray(np.stack([b["labels"] for b in eval_batches]))

    ref = None
    if fidelity:
        from .specs import BackendSpec
        golden = ApproxPolicy(default=BackendSpec.golden().materialize())
        ref = jnp.stack([
            jax.jit(lambda i=i: resnet.forward(params, images[i], cfg,
                                               golden))()
            for i in range(images.shape[0])])

    def traceable_metrics(policy):
        # one loop body over the eval batches (lax.map), not one copy
        # of the network per batch: a fraction of the compile time
        def batch_metrics(i):
            logits = resnet.forward(params, images[i], cfg, policy)
            acc = jnp.mean((jnp.argmax(logits, -1) == labels[i])
                           .astype(jnp.float32))
            if ref is None:
                return acc, acc
            return acc, jnp.mean(jnp.abs(logits - ref[i]))

        accs, maes = jax.lax.map(batch_metrics,
                                 jnp.arange(images.shape[0]))
        out = {"accuracy": jnp.mean(accs)}
        if ref is not None:
            out["logit_mae"] = jnp.mean(maes)
        return out

    def fn(policy):
        out = jax.jit(lambda: traceable_metrics(policy))()
        return {k: float(v) for k, v in out.items()}

    base_name = f"classification[resnet{getattr(cfg, 'depth', '')}]"
    if not fidelity:
        return Workload(
            name=name or base_name,
            fn=fn, metrics=("accuracy",),
            traceable_metrics=traceable_metrics,
            directions={"accuracy": "max"},
            layer_counts=resnet.layer_mult_counts(cfg))
    return Workload(
        name=name or f"{base_name}+fidelity",
        fn=fn, metrics=("logit_mae", "accuracy"), primary="logit_mae",
        traceable_metrics=traceable_metrics,
        directions={"logit_mae": "min", "accuracy": "max"},
        layer_counts=resnet.layer_mult_counts(cfg))


def logit_fidelity(forward, inputs: Sequence[Any], *,
                   ref_policy: ApproxPolicy = EXACT_POLICY,
                   name: str = "logit_fidelity",
                   layer_counts: Optional[dict[str, int]] = None
                   ) -> Workload:
    """Logit fidelity vs a reference datapath (default: exact f32).

    ``forward(policy, x) -> logits`` is the model closure; ``inputs``
    the eval batches.  Metrics:

      * ``logit_mae`` (minimize) — mean over batches of the per-batch
        mean |logits − reference|, the continuous axis where
        quantization/datapath width shows while top-1 accuracy
        saturates (DESIGN.md §2.6);
      * ``top1_agreement`` (maximize) — fraction of argmax decisions
        matching the reference.

    The reference logits are computed once, eagerly, at construction.
    """
    import jax
    import jax.numpy as jnp

    inputs = list(inputs)
    ref = [forward(ref_policy, x) for x in inputs]

    def traceable_metrics(policy):
        maes, agree = [], []
        for x, r in zip(inputs, ref):
            logits = forward(policy, x)
            maes.append(jnp.mean(jnp.abs(logits - r)))
            agree.append(jnp.mean(
                (jnp.argmax(logits, -1) == jnp.argmax(r, -1))
                .astype(jnp.float32)))
        return {"logit_mae": jnp.mean(jnp.stack(maes)),
                "top1_agreement": jnp.mean(jnp.stack(agree))}

    def fn(policy):
        out = jax.jit(lambda: traceable_metrics(policy))()
        return {k: float(v) for k, v in out.items()}

    return Workload(name=name, fn=fn,
                    metrics=("logit_mae", "top1_agreement"),
                    primary="logit_mae",
                    traceable_metrics=traceable_metrics,
                    directions={"logit_mae": "min",
                                "top1_agreement": "max"},
                    layer_counts=layer_counts)


def _lm_setup(cfg, params, seed: int):
    """Resolve (cfg, params, model fns) for the LM adapters; ``cfg``
    may be an ``LMConfig`` or a registered arch name (resolved through
    ``repro.configs.get_config(...).reduced()`` so adapters stay
    smoke-test sized by default).  Every registered family works —
    non-token inputs (whisper frame embeddings, llava image embeddings)
    come from ``registry.input_extras`` and are merged into each eval
    batch."""
    import jax

    from repro.models.registry import model_fns

    if isinstance(cfg, str):
        from repro.configs import get_config
        cfg = get_config(cfg).reduced()
    fns = model_fns(cfg)
    if params is None:
        params = fns.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params, fns


def _lm_token_batches(cfg, batch: int, seq_len: int, n_batches: int,
                      seed: int):
    import jax.numpy as jnp

    from repro.data.synthetic import token_stream
    from repro.models.registry import input_extras

    extras = input_extras(cfg, batch)
    out = []
    for i in range(n_batches):
        tokens, targets = token_stream(cfg.vocab, batch, seq_len,
                                       step=i, seed=seed)
        out.append({"tokens": jnp.asarray(tokens),
                    "targets": jnp.asarray(targets), **extras})
    return out


# ----------------------------------------------------------------------
# Unified MAC accounting (the Workload.layer_counts protocol;
# DESIGN.md §2.12)
# ----------------------------------------------------------------------
def _merge_counts(dst: dict, src: Mapping[str, int], scale: int = 1):
    for tag, c in src.items():
        dst[tag] = dst.get(tag, 0) + int(c) * scale


def _attn_counts(cfg, t: int, prefix: str = "attn") -> dict[str, int]:
    from .layers import dense_mult_count
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        f"{prefix}.wq": dense_mult_count((t, d), (d, h * hd)),
        f"{prefix}.wk": dense_mult_count((t, d), (d, hk * hd)),
        f"{prefix}.wv": dense_mult_count((t, d), (d, hk * hd)),
        f"{prefix}.wo": dense_mult_count((t, h * hd), (h * hd, d)),
    }


def _mla_counts(cfg, t: int) -> dict[str, int]:
    from .layers import dense_mult_count
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora, cfg.kv_lora
    return {
        "mla.wdq": dense_mult_count((t, d), (d, ql)),
        "mla.wuq": dense_mult_count((t, ql), (ql, h * dn)),
        "mla.wqr": dense_mult_count((t, ql), (ql, h * dr)),
        "mla.wdkv": dense_mult_count((t, d), (d, kl)),
        "mla.wuk": dense_mult_count((t, kl), (kl, h * dn)),
        "mla.wuv": dense_mult_count((t, kl), (kl, h * dv)),
        "mla.wkr": dense_mult_count((t, d), (d, dr)),
        "mla.wo": dense_mult_count((t, h * dv), (h * dv, d)),
    }


def _ffn_counts(cfg, t: int, prefix: str = "ffn",
                d_ff: Optional[int] = None) -> dict[str, int]:
    from .layers import dense_mult_count
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    counts = {
        f"{prefix}.wi": dense_mult_count((t, d), (d, f)),
        f"{prefix}.wo": dense_mult_count((t, f), (f, d)),
    }
    if cfg.act == "silu":
        counts[f"{prefix}.wg"] = dense_mult_count((t, d), (d, f))
    return counts


def _moe_counts(cfg, t: int) -> dict[str, int]:
    """Expert MACs mirror the sort-based dispatch exactly: every expert
    processes its full capacity buffer (zero-padded slots multiply
    too), so the per-projection cost is ``nb * E * C * d * f`` with the
    same blocked/unblocked capacity arithmetic as ``models.moe``.  The
    router einsum stays exact (f32) and carries no approximate MACs."""
    import math
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    nb = cfg.moe_blocks
    if nb > 1 and t % nb == 0 and t // nb >= k:
        tb = t // nb
    else:
        nb, tb = 1, t
    cap = int(min(tb * k,
                  max(math.ceil(tb * k / e * cfg.capacity_factor), 4)))
    per = nb * e * cap
    counts = {"moe.wi": per * d * f, "moe.wo": per * f * d}
    if cfg.act == "silu":
        counts["moe.wg"] = per * d * f
    if cfg.n_shared_experts > 0:
        counts.update(_ffn_counts(cfg, t, prefix="moe.shared",
                                  d_ff=f * cfg.n_shared_experts))
    return counts


def _mamba_counts(cfg, t: int) -> dict[str, int]:
    from .layers import dense_mult_count

    from repro.models.mamba2 import ssm_dims
    dd = ssm_dims(cfg)
    d, di = cfg.d_model, dd["d_inner"]
    d_proj = 2 * di + 2 * dd["n"] + dd["n_heads"]
    return {
        "mamba.in_proj": dense_mult_count((t, d), (d, d_proj)),
        "mamba.out_proj": dense_mult_count((t, di), (di, d)),
    }


def _encdec_mult_counts(cfg, batch: int, seq_len: int) -> dict[str, int]:
    from .layers import dense_mult_count
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    t_enc = batch * cfg.enc_frames
    t_dec = batch * seq_len
    counts: dict[str, int] = {}
    _merge_counts(counts, _attn_counts(cfg, t_enc, prefix="enc.attn"),
                  cfg.n_enc_layers)
    _merge_counts(counts, _ffn_counts(cfg, t_enc, prefix="enc.ffn"),
                  cfg.n_enc_layers)
    _merge_counts(counts, _attn_counts(cfg, t_dec, prefix="dec.attn"),
                  cfg.n_layers)
    _merge_counts(counts, _ffn_counts(cfg, t_dec, prefix="dec.ffn"),
                  cfg.n_layers)
    # Cross-attention: queries/output over decoder positions, cross-KV
    # over encoder frames, once per decoder layer.
    _merge_counts(counts, {
        "xattn.wq": dense_mult_count((t_dec, d), (d, h * hd)),
        "xattn.wk": dense_mult_count((t_enc, d), (d, h * hd)),
        "xattn.wv": dense_mult_count((t_enc, d), (d, h * hd)),
        "xattn.wo": dense_mult_count((t_dec, h * hd), (h * hd, d)),
    }, cfg.n_layers)
    return counts


def _resnet_mult_counts(cfg, batch: int) -> dict[str, int]:
    from .layers import conv_mult_count, dense_mult_count
    counts: dict[str, int] = {}
    size = cfg.image_size
    counts["conv_init"] = conv_mult_count((batch, size, size, 3),
                                          (3, 3, 3, cfg.widths[0]))
    cin = cfg.widths[0]
    for s, width in enumerate(cfg.widths):
        for b in range(cfg.n_blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            out_size = size // stride
            counts[f"s{s}_b{b}_conv1"] = conv_mult_count(
                (batch, size, size, cin), (3, 3, cin, width), stride)
            counts[f"s{s}_b{b}_conv2"] = conv_mult_count(
                (batch, out_size, out_size, width), (3, 3, width, width))
            if cin != width:
                counts[f"s{s}_b{b}_proj"] = conv_mult_count(
                    (batch, size, size, cin), (1, 1, cin, width), stride)
            size = out_size
            cin = width
    counts["head"] = dense_mult_count((batch, cfg.widths[-1]),
                                      (cfg.widths[-1], cfg.n_classes))
    return counts


def layer_mult_counts(cfg, batch: int = 1,
                      seq_len: int = 16) -> dict[str, int]:
    """Per-layer-tag multiplication counts for ANY model the repo ships
    — the single MAC-accounting implementation behind the
    ``Workload.layer_counts`` protocol (DESIGN.md §2.12).

    ``cfg`` is a ``ResNetConfig`` (``seq_len`` ignored) or any
    ``LMConfig`` family (dense/moe/ssm/hybrid/vlm/encdec).  Layer tags
    are shared across scanned blocks ("attn.wq", "moe.wi", ...), so
    each tag's count aggregates over every block that uses it —
    mirroring ``models.decoder.block_pattern`` slot by slot — and
    non-token inputs count the way the adapters feed them
    (``registry.input_extras``): vlm prefixes ``n_img_tokens`` image
    positions (plus the ``img_proj`` projection itself), encdec runs
    the encoder over ``enc_frames`` per batch element.  Exact einsums
    (norms, attention scores, the MoE router, the SSM scan) carry no
    approximate MACs and do not appear."""
    if hasattr(cfg, "widths"):          # ResNetConfig, without an import
        return _resnet_mult_counts(cfg, batch)
    if cfg.family == "encdec":
        return _encdec_mult_counts(cfg, batch, seq_len)

    from repro.models.decoder import block_pattern

    # vlm image embeddings are PREPENDED to the token sequence, so every
    # decoder projection also runs over those positions.
    extra = cfg.n_img_tokens if cfg.family == "vlm" else 0
    t = batch * (seq_len + extra)
    pattern = block_pattern(cfg)
    reps = cfg.n_layers // len(pattern)
    per_group: dict[str, int] = {}
    for mixer, ffn_kind in pattern:
        if mixer == "attn":
            _merge_counts(per_group, _attn_counts(cfg, t))
        elif mixer == "mla":
            _merge_counts(per_group, _mla_counts(cfg, t))
        else:
            _merge_counts(per_group, _mamba_counts(cfg, t))
        if ffn_kind == "ffn":
            _merge_counts(per_group, _ffn_counts(cfg, t))
        elif ffn_kind == "moe":
            _merge_counts(per_group, _moe_counts(cfg, t))
    counts = {tag: c * reps for tag, c in per_group.items()}
    if cfg.family == "vlm" and cfg.n_img_tokens > 0:
        from .layers import dense_mult_count
        counts["img_proj"] = dense_mult_count(
            (batch * cfg.n_img_tokens, cfg.d_model),
            (cfg.d_model, cfg.d_model))
    return counts


def lm_layer_mult_counts(cfg, batch: int, seq_len: int) -> dict[str, int]:
    """Pre-§2.12 name for ``layer_mult_counts`` on LM configs (kept as
    a shim for existing call sites)."""
    return layer_mult_counts(cfg, batch=batch, seq_len=seq_len)


def lm_fidelity(cfg: Union[str, Any], params=None, *, batch: int = 2,
                seq_len: int = 16, n_batches: int = 2,
                seed: int = 0) -> Workload:
    """Decoder logit fidelity vs the f32 model: prefill the LM on
    deterministic synthetic token batches and compare the last-position
    logits against the exact-datapath reference — ``logit_mae``
    (minimize, primary) + ``top1_agreement`` (maximize), the metric
    pair previously inlined in ``benchmarks/wide_width_pareto.py``, now
    over ANY registered decoder config."""
    from repro.models.registry import prompt_extra_len

    cfg, params, fns = _lm_setup(cfg, params, seed)
    batches = _lm_token_batches(cfg, batch, seq_len, n_batches, seed)
    max_len = seq_len + prompt_extra_len(cfg, batches[0])

    def forward(policy, b):
        cache = fns.init_cache(cfg, batch, max_len)
        logits, _ = fns.forward_prefill(params, b, cache, cfg, policy)
        return logits

    return logit_fidelity(
        forward, batches, name=f"lm_fidelity[{cfg.name}]",
        layer_counts=layer_mult_counts(cfg, batch, seq_len))


def lm_perplexity(cfg: Union[str, Any], params=None, *, batch: int = 2,
                  seq_len: int = 16, n_batches: int = 2,
                  seed: int = 0) -> Workload:
    """Decoder LM loss/perplexity on deterministic synthetic token
    batches: ``perplexity`` (minimize, primary) = exp(mean CE loss),
    plus the raw ``loss``.  An untrained tiny config still yields a
    meaningful *relative* axis — approximation error moves the loss."""
    import jax
    import jax.numpy as jnp

    cfg, params, fns = _lm_setup(cfg, params, seed)
    batches = _lm_token_batches(cfg, batch, seq_len, n_batches, seed)

    def traceable_metrics(policy):
        losses = [fns.forward_train(params, b, cfg, policy)
                  for b in batches]
        loss = jnp.mean(jnp.stack(losses))
        return {"perplexity": jnp.exp(loss), "loss": loss}

    def fn(policy):
        out = jax.jit(lambda: traceable_metrics(policy))()
        return {k: float(v) for k, v in out.items()}

    return Workload(name=f"lm_perplexity[{cfg.name}]", fn=fn,
                    metrics=("perplexity", "loss"), primary="perplexity",
                    traceable_metrics=traceable_metrics,
                    directions={"perplexity": "min", "loss": "min"},
                    layer_counts=layer_mult_counts(cfg, batch, seq_len))
