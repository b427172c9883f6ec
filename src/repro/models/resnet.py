"""CIFAR-style ResNet family (paper Sec. IV, Fig. 3): 3 stages of n
residual blocks with widths 16/32/64 — depth = 6n+2 (ResNet-8 ... 50).

Every convolution runs through ``repro.approx.layers.conv2d`` (im2col +
backend matmul), so any conv layer can be switched to any approximate
multiplier — the exact experiment of the paper.  Normalization is
batch-statistics BN (pure functional; no running stats), which is
adequate for the synthetic-CIFAR reproduction and keeps params a plain
pytree.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx.layers import ApproxPolicy, EXACT_POLICY, conv2d
from .common import dense_init, split_keys


@dataclass(frozen=True)
class ResNetConfig:
    n_blocks: int = 1                   # blocks per stage; depth = 6n+2
    widths: tuple = (16, 32, 64)
    n_classes: int = 10
    image_size: int = 32
    norm_eps: float = 1e-5

    @property
    def depth(self) -> int:
        return 6 * self.n_blocks + 2

    @property
    def name(self) -> str:
        return f"resnet{self.depth}"


def resnet_config(depth: int) -> ResNetConfig:
    assert (depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
    return ResNetConfig(n_blocks=(depth - 2) // 6)


def _conv_init(key, kh, kw, cin, cout):
    fan = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) \
        * np.sqrt(2.0 / fan)


def init_params(key, cfg: ResNetConfig) -> dict:
    keys = jax.random.split(key, 2 + 3 * cfg.n_blocks * 3 + 4)
    ki = iter(range(len(keys)))
    params = {
        "conv_init": {"w": _conv_init(keys[next(ki)], 3, 3, 3,
                                      cfg.widths[0]),
                      "bn_g": jnp.ones((cfg.widths[0],)),
                      "bn_b": jnp.zeros((cfg.widths[0],))},
    }
    cin = cfg.widths[0]
    for s, width in enumerate(cfg.widths):
        for b in range(cfg.n_blocks):
            blk = {
                "conv1": {"w": _conv_init(keys[next(ki)], 3, 3, cin, width),
                          "bn_g": jnp.ones((width,)),
                          "bn_b": jnp.zeros((width,))},
                "conv2": {"w": _conv_init(keys[next(ki)], 3, 3, width,
                                          width),
                          "bn_g": jnp.ones((width,)),
                          "bn_b": jnp.zeros((width,))},
            }
            if cin != width:
                blk["proj"] = {"w": _conv_init(keys[next(ki)], 1, 1, cin,
                                               width)}
            params[f"s{s}_b{b}"] = blk
            cin = width
    params["head"] = {
        "w": dense_init(keys[next(ki)], (cfg.widths[-1], cfg.n_classes)),
        "b": jnp.zeros((cfg.n_classes,)),
    }
    return params


def _ordered_mean(x, axes):
    """Mean over ``axes`` (kept as size 1) summed as a fixed binary tree
    of elementwise adds.  An f32 reduce may add in any order, and on a
    TPU XLA takes the order from the operand's layout, which differs
    between programs (a banked sweep and the sequential one, or two
    datapaths); the tree fixes the order, so every program computes the
    same bits — which the bit-identical sweeps rely on."""
    keep = tuple(d for d in range(x.ndim) if d not in axes)
    n = int(np.prod([x.shape[a] for a in axes]))
    y = jnp.transpose(x, tuple(axes) + keep)
    y = y.reshape((n,) + tuple(x.shape[d] for d in keep))
    y = jnp.pad(y, [(0, (1 << (n - 1).bit_length()) - n)]
                + [(0, 0)] * len(keep))
    while y.shape[0] > 1:
        y = y[:y.shape[0] // 2] + y[y.shape[0] // 2:]
    shape = [1 if d in axes else x.shape[d] for d in range(x.ndim)]
    return (y[0] / n).reshape(shape)


def _bn(x, g, b, eps):
    mu = _ordered_mean(x, (0, 1, 2))
    var = _ordered_mean(jnp.square(x - mu), (0, 1, 2))
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def forward(params, images, cfg: ResNetConfig,
            policy: ApproxPolicy = EXACT_POLICY) -> jax.Array:
    """images: (B,H,W,3) f32 -> logits (B, n_classes)."""
    x = conv2d(policy, "conv_init", images, params["conv_init"]["w"])
    x = _bn(x, params["conv_init"]["bn_g"], params["conv_init"]["bn_b"],
            cfg.norm_eps)
    x = jax.nn.relu(x)
    cin = cfg.widths[0]
    for s, width in enumerate(cfg.widths):
        for b in range(cfg.n_blocks):
            name = f"s{s}_b{b}"
            blk = params[name]
            stride = 2 if (s > 0 and b == 0) else 1
            y = conv2d(policy, f"{name}_conv1", x, blk["conv1"]["w"],
                       stride=stride)
            y = _bn(y, blk["conv1"]["bn_g"], blk["conv1"]["bn_b"],
                    cfg.norm_eps)
            y = jax.nn.relu(y)
            y = conv2d(policy, f"{name}_conv2", y, blk["conv2"]["w"])
            y = _bn(y, blk["conv2"]["bn_g"], blk["conv2"]["bn_b"],
                    cfg.norm_eps)
            if "proj" in blk:
                sc = conv2d(policy, f"{name}_proj", x, blk["proj"]["w"],
                            stride=stride)
            else:
                sc = x
            x = jax.nn.relu(y + sc)
            cin = width
    x = _ordered_mean(x, (1, 2)).reshape(x.shape[0], x.shape[3])
    return policy.matmul("head", x, params["head"]["w"]) + params["head"]["b"]


def layer_mult_counts(cfg: ResNetConfig, batch: int = 1) -> dict[str, int]:
    """Per-conv-layer multiplication counts (the paper's Fig. 4 shares).
    Layer names match the policy tags in ``forward``.  Shim over the
    unified ``repro.approx.workload.layer_mult_counts`` accounting
    (DESIGN.md §2.12), preserving the historical conv-only contract —
    the unified map also counts the ``head`` matmul."""
    from repro.approx.workload import layer_mult_counts as unified
    counts = unified(cfg, batch=batch)
    counts.pop("head", None)
    return counts


def loss_fn(params, batch, cfg: ResNetConfig,
            policy: ApproxPolicy = EXACT_POLICY) -> jax.Array:
    logits = forward(params, batch["images"], cfg, policy)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def accuracy(params, batch, cfg: ResNetConfig,
             policy: ApproxPolicy = EXACT_POLICY) -> jax.Array:
    logits = forward(params, batch["images"], cfg, policy)
    return jnp.mean((jnp.argmax(logits, axis=-1) == batch["labels"]
                     ).astype(jnp.float32))
