"""Plain reference of the banked sweep's rows for the CIFAR ResNet family.

It imports nothing of the program and takes nothing the program made:
the eval images come from a copy of the synthetic-CIFAR generator, each
multiplier's product table from a simulation of its gate netlist
(``bench/netlist.py``), and the weights from the benchmark
(``bench/models/resnet_cifar.py``).

A row is one multiplier evaluated on the eval images, in every layer
(Table II) or in one layer with every other layer exact (Fig. 4).  Its
numbers are those the program's ``classification(..., fidelity=True)``
workload reports, computed as the paper's datapath defines them:

* a matmul quantizes both operands to unsigned 8-bit codes by min/max
  calibration, sums the multiplier's products ``LUT[a, w]`` in int32
  and removes the zero points: in int32 then float32 on the exact
  datapath, in float32 with each correction term truncated on a LUT
  datapath; then scales by both operands' steps;
* convolutions are 3x3 (1x1 projections) im2col matmuls with SAME
  padding; batch norm uses each eval batch's statistics;
* ``logit_mae`` is the mean over eval batches of the mean absolute gap
  between the row's logits and the exact datapath's, and ``accuracy``
  the mean over eval batches of the top-1 hit rate.

Every float step runs in ``dtype``: float32 for the reference, and a
lower precision for the control that the comparison has to reject.
The product sums are exact integers either way.  Matmuls of floats do
not occur (every product is a table lookup), and the default precision
is set to the highest all the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.netlist import exact_lut

#: rows of the product table gathered at once (bounds the gather's
#: intermediate to rows * K * N int32)
_ROW_BLOCK = 1024


def synthetic_cifar(split: str, n: int, seed: int = 0, image_size: int = 32,
                    n_classes: int = 10):
    """Copy of the repository's synthetic CIFAR generator
    (``repro.data.synthetic.synthetic_cifar``, data version 2): class
    gratings and a blob per image, with per-image jitter and noise.
    Returns (images (n, S, S, 3) float32 in [0, 1], labels (n,) int32)."""
    base = 0xC1FA9 if split == "train" else 0x7E57
    rng = np.random.default_rng(base + seed)
    s = image_size
    yy, xx = np.mgrid[0:s, 0:s].astype(np.float32) / s
    prng = np.random.default_rng(1234)
    freqs = prng.uniform(2.0, 6.0, size=(n_classes, 3))
    angles = prng.uniform(0, np.pi, size=(n_classes, 3))
    phases = prng.uniform(0, 2 * np.pi, size=(n_classes, 3))
    centers = prng.uniform(0.25, 0.75, size=(n_classes, 2))
    colors = prng.uniform(0.4, 1.0, size=(n_classes, 3))
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    images = np.empty((n, s, s, 3), dtype=np.float32)
    for i in range(n):
        c = labels[i]
        img = np.zeros((s, s, 3), np.float32)
        jitter = rng.normal(0, 0.22, size=2)
        cx, cy = centers[c] + jitter
        blob = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / 0.04))
        for ch in range(3):
            a = angles[c, ch] + rng.normal(0, 0.35)
            f = freqs[c, ch] * (1.0 + rng.normal(0, 0.15))
            grating = np.sin(2 * np.pi * f
                             * (xx * np.cos(a) + yy * np.sin(a))
                             + phases[c, ch] + rng.normal(0, 0.8))
            img[:, :, ch] = 0.5 + 0.10 * grating * colors[c, ch] \
                + 0.16 * blob * colors[c, (ch + 1) % 3]
        img += rng.normal(0, 0.16, size=(s, s, 3))
        images[i] = np.clip(img, 0.0, 1.0)
    return images, labels


def layer_names(cfg: dict) -> list[str]:
    """The approximable layers in forward order, then the head."""
    names = ["conv_init"]
    cin = cfg["widths"][0]
    for s, width in enumerate(cfg["widths"]):
        for b in range(cfg["n_blocks"]):
            names += [f"s{s}_b{b}_conv1", f"s{s}_b{b}_conv2"]
            if cin != width:
                names.append(f"s{s}_b{b}_proj")
            cin = width
    return names + ["head"]


def _lut_sum(qa, qw, lut):
    """Σ_k LUT[qa[m, k], qw[k, n]] in int32: each (m, k) gathers the
    N-wide row ``LUT[qa[m, k], qw[k, :]]``."""
    m, k = qa.shape
    n = qw.shape[1]
    rows = jnp.transpose(jnp.take(lut, qw, axis=1), (1, 0, 2))
    rows = rows.reshape(k * 256, n)
    idx = qa + 256 * jnp.arange(k, dtype=jnp.int32)[None, :]
    blk = min(_ROW_BLOCK, m)
    pad = -m % blk
    idx = jnp.pad(idx, ((0, pad), (0, 0))).reshape(-1, blk, k)
    out = jax.lax.map(
        lambda i: jnp.sum(jnp.take(rows, i, axis=0), axis=1,
                          dtype=jnp.int32), idx)
    return out.reshape(-1, n)[:m]


def _calibrate(x, dtype):
    lo = jnp.minimum(jnp.min(x), 0).astype(dtype)
    hi = jnp.maximum(jnp.max(x), 0).astype(dtype)
    scale = jnp.maximum((hi - lo) / jnp.asarray(255, dtype),
                        jnp.asarray(1e-8, dtype))
    zp = jnp.clip(jnp.round(-lo / scale), 0, 255).astype(jnp.int32)
    return scale, zp


def _quantize(x, scale, zp, dtype):
    q = jnp.round(x / scale) + zp.astype(dtype)
    return jnp.clip(q, 0, 255).astype(jnp.int32)


def _matmul(x, w, lut, approx, dtype):
    """Quantized matmul of (M, K) x (K, N) through ``lut``; ``approx``
    (traced bool) picks the LUT datapath's zero-point removal over the
    exact datapath's."""
    x = x.astype(dtype)
    w = w.astype(dtype)
    sa, za = _calibrate(x, dtype)
    sw, zw = _calibrate(w, dtype)
    qa, qw = _quantize(x, sa, za, dtype), _quantize(w, sw, zw, dtype)
    k = x.shape[1]
    s = _lut_sum(qa, qw, lut)
    row = jnp.sum(qa, axis=1, dtype=jnp.int32)[:, None]
    col = jnp.sum(qw, axis=0, dtype=jnp.int32)[None, :]
    exact = (s - zw * row - za * col + k * za * zw).astype(dtype)
    zaf, zwf = za.astype(dtype), zw.astype(dtype)
    lut_path = (s.astype(dtype) - jnp.trunc(zwf * row.astype(dtype))
                - jnp.trunc(zaf * col.astype(dtype))
                + jnp.trunc(k * zaf * zwf))
    return jnp.where(approx, lut_path, exact) * (sa * sw)


def _conv(x, w, lut, approx, stride, dtype):
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + kh - h, 0)
    pw = max((wo - 1) * stride + kw - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    cols = [xp[:, i:i + (ho - 1) * stride + 1:stride,
               j:j + (wo - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(cols, axis=-1).reshape(-1, kh * kw * cin)
    y = _matmul(patches, w.reshape(kh * kw * cin, cout), lut, approx, dtype)
    return y.reshape(b, ho, wo, cout)


def _mean(x, axes):
    """Mean over ``axes`` (kept, size 1), summed as the datapath sums
    it: pairwise over a binary tree of elementwise adds, the terms in
    row-major order of ``axes`` and zero-padded to a power of two."""
    keep = tuple(d for d in range(x.ndim) if d not in axes)
    n = int(np.prod([x.shape[a] for a in axes]))
    y = jnp.transpose(x, tuple(axes) + keep).reshape(
        (n,) + tuple(x.shape[d] for d in keep))
    y = jnp.pad(y, [(0, (1 << (n - 1).bit_length()) - n)]
                + [(0, 0)] * len(keep))
    while y.shape[0] > 1:
        y = y[:y.shape[0] // 2] + y[y.shape[0] // 2:]
    return (y[0] / n).reshape([1 if d in axes else x.shape[d]
                               for d in range(x.ndim)])


def _bn(x, p, eps, dtype):
    mu = _mean(x, (0, 1, 2))
    var = _mean(jnp.square(x - mu), (0, 1, 2))
    return ((x - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, dtype))
            * p["bn_g"].astype(dtype) + p["bn_b"].astype(dtype))


def forward(params, images, luts, sel, cfg: dict, dtype=jnp.float32):
    """Logits (B, n_classes) in float32.  ``luts`` (L, 256, 256) int32,
    ``sel`` (n_layers + 1,) int32: layer ``j`` of ``layer_names`` runs
    ``luts[sel[j]]``, on the LUT datapath where ``sel[j] > 0`` and on
    the exact one (``luts[0]`` is the exact product) where it is 0."""
    names = layer_names(cfg)
    idx = {name: j for j, name in enumerate(names)}
    eps = cfg["norm_eps"]

    def conv(name, x, w, stride=1):
        return _conv(x, w, luts[sel[idx[name]]], sel[idx[name]] > 0,
                     stride, dtype)

    x = conv("conv_init", images.astype(dtype), params["conv_init"]["w"])
    x = jax.nn.relu(_bn(x, params["conv_init"], eps, dtype))
    cin = cfg["widths"][0]
    for s, width in enumerate(cfg["widths"]):
        for b in range(cfg["n_blocks"]):
            name = f"s{s}_b{b}"
            blk = params[name]
            stride = 2 if (s > 0 and b == 0) else 1
            y = conv(f"{name}_conv1", x, blk["conv1"]["w"], stride)
            y = jax.nn.relu(_bn(y, blk["conv1"], eps, dtype))
            y = conv(f"{name}_conv2", y, blk["conv2"]["w"])
            y = _bn(y, blk["conv2"], eps, dtype)
            sc = (conv(f"{name}_proj", x, blk["proj"]["w"], stride)
                  if cin != width else x)
            x = jax.nn.relu(y + sc)
            cin = width
    x = _mean(x, (1, 2)).reshape(x.shape[0], x.shape[3])
    j = idx["head"]
    logits = _matmul(x, params["head"]["w"], luts[sel[j]], sel[j] > 0,
                     dtype) + params["head"]["b"].astype(dtype)
    return logits.astype(jnp.float32)


class Reference:
    """The reference (``dtype=float32``) or its lower-precision control
    over the eval images the traffic mix asks for.  ``rows`` evaluates rows
    ``(multiplier LUT, layer or "all")``; one compiled program serves
    every row, with the LUTs and the layer choice as its arguments."""

    def __init__(self, cfg: dict, params, traffic: dict,
                 dtype=jnp.float32):
        n_images, batch = traffic["eval_images"], traffic["eval_batch"]
        images, labels = synthetic_cifar("test", n_images)
        nb = n_images // batch
        self.cfg = cfg
        self.names = layer_names(cfg)
        self.images = jnp.asarray(images[:nb * batch]).reshape(
            nb, batch, *images.shape[1:])
        self.labels = jnp.asarray(labels[:nb * batch]).reshape(nb, batch)
        self.params = jax.tree.map(jnp.asarray, params)
        self.dtype = dtype
        self._logits = jax.jit(functools.partial(self._all_logits, cfg=cfg,
                                                 dtype=dtype))
        exact = jnp.asarray(np.stack([exact_lut(), exact_lut()]))
        self.golden = self.logits(exact, np.zeros(len(self.names), np.int32))

    @staticmethod
    def _all_logits(params, images, luts, sel, cfg, dtype):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda im: forward(params, im, luts, sel, cfg, dtype),
                images)

    def logits(self, luts, sel):
        return self._logits(self.params, self.images, luts,
                            jnp.asarray(sel, jnp.int32))

    def row(self, lut: np.ndarray, layer: str) -> dict:
        """``{"logit_mae", "accuracy"}`` of one row."""
        luts = jnp.asarray(np.stack([exact_lut(), lut]))
        sel = np.zeros(len(self.names), np.int32)
        if layer == "all":
            sel[:] = 1
        else:
            sel[self.names.index(layer)] = 1
        logits = self.logits(luts, sel)
        mae = jnp.mean(jnp.mean(jnp.abs(logits - self.golden), axis=(1, 2)))
        acc = jnp.mean(jnp.mean((jnp.argmax(logits, -1) == self.labels)
                                .astype(jnp.float32), axis=1))
        return {"logit_mae": float(mae), "accuracy": float(acc)}

    def logit_scale(self) -> float:
        """Mean |logit| of the exact datapath: the scale the gaps are
        measured against."""
        return float(jnp.mean(jnp.abs(self.golden)))
