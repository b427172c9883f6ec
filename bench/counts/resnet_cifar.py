"""Counts of the CIFAR ResNet family: the conv shapes follow He et al.
(2016, section 4.2) as the configuration file states them.

* ``conv_shapes``: the (M, K, N) im2col matmul of every conv layer at
  ``batch`` images (3x3 convs, SAME padding, stride 2 entering every
  stage after the first, a 1x1 projection where the width changes).
* ``macs_per_item``: multiply-accumulates of one image, convs and the
  classifier head.
* ``program_lut_matmuls``: every LUT matmul one banked sweep program
  launches, per lane: its layers' matmuls once per eval batch.
"""
from __future__ import annotations


def conv_shapes(cfg: dict, batch: int = 1) -> dict[str, tuple[int, int, int]]:
    """(M, K, N) of every conv layer, in forward order, keyed by the
    layer names the sweep reports."""
    side = cfg["image_size"]
    widths = cfg["widths"]
    shapes = {"conv_init": (batch * side * side, 9 * cfg["in_channels"],
                            widths[0])}
    cin = widths[0]
    for s, width in enumerate(widths):
        for b in range(cfg["n_blocks"]):
            if s > 0 and b == 0:
                side //= 2
            m = batch * side * side
            shapes[f"s{s}_b{b}_conv1"] = (m, 9 * cin, width)
            shapes[f"s{s}_b{b}_conv2"] = (m, 9 * width, width)
            if cin != width:
                shapes[f"s{s}_b{b}_proj"] = (m, cin, width)
            cin = width
    return shapes


def head_shape(cfg: dict, batch: int = 1) -> tuple[int, int, int]:
    return (batch, cfg["widths"][-1], cfg["n_classes"])


def macs_per_item(cfg: dict) -> int:
    """Multiply-accumulates of one image's forward pass."""
    m, k, n = head_shape(cfg)
    return sum(m * k * n for m, k, n in conv_shapes(cfg).values()) \
        + m * k * n


def eval_items(cfg: dict, traffic: dict) -> int:
    return traffic["eval_images"]


def program_lut_matmuls(cfg: dict, traffic: dict, layer: str | None
                        ) -> list[tuple[str, int, int, int, bool]]:
    """The LUT matmuls of one banked program, per lane:
    ``(layer, M, K, N, shared_codes)`` for each eval batch.

    ``layer=None`` is an all-layers (Table II) program: every conv and
    the head run the lane's LUT, and only the first conv reads codes
    that every lane shares.  A layer name is a per-layer (Fig. 4)
    program: that layer alone runs the LUT, on codes every lane shares,
    and the rest run the exact int8 product."""
    batch = traffic["eval_batch"]
    shapes = conv_shapes(cfg, batch)
    if layer is not None:
        per_batch = [(layer, *shapes[layer], True)]
    else:
        per_batch = [(name, m, k, n, i == 0)
                     for i, (name, (m, k, n)) in enumerate(shapes.items())]
        per_batch.append(("head", *head_shape(cfg, batch), False))
    return per_batch * (traffic["eval_images"] // batch)
