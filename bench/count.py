"""Work a sweep needs, counted from a configuration's shapes.

Nothing here reads the program: the conv shapes follow the CIFAR ResNet
of He et al. (2016, section 4.2) as the configuration file states it,
so that a change to the program cannot change the yardstick.

* ``conv_shapes``: the (M, K, N) im2col matmul of every conv layer at
  ``batch`` images (3x3 convs, SAME padding, stride 2 entering every
  stage after the first, a 1x1 projection where the width changes).
* ``macs_per_image``: multiply-accumulates of one forward pass, convs
  and the classifier head.
* ``lut_matmul_work``: operations and bytes of one LUT matmul, the work
  it needs whatever implements it: ``2*M*K*N`` operations; uint8 codes
  and weights, the lane's 256x256 int32 LUT and int32 outputs.
* ``program_lut_matmuls``: every LUT matmul one banked sweep program
  executes, per lane and eval batch.
"""
from __future__ import annotations

LUT_BYTES = 256 * 256 * 4


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


def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image's forward pass."""
    m, k, n = head_shape(cfg)
    return sum(m * k * n for m, k, n in conv_shapes(cfg).values()) \
        + m * k * n


def lut_matmul_work(m: int, k: int, n: int, shared_codes: bool = False,
                    lanes: int = 1) -> tuple[int, int]:
    """(operations, bytes) of ``lanes`` LUT matmuls of one (M, K, N)
    shape.  Codes shared by every lane are read once."""
    ops = 2 * m * k * n * lanes
    codes = m * k * (1 if shared_codes else lanes)
    per_lane = k * n + LUT_BYTES + 4 * m * n
    return ops, codes + per_lane * lanes


def program_lut_matmuls(cfg: dict, batch: int, layer: str | None
                        ) -> list[tuple[str, int, int, int, bool]]:
    """The LUT matmuls of one banked program, per eval batch and lane:
    ``(layer, M, K, N, shared_codes)``.

    ``layer=None`` is an all-layers (Table II) program: every conv and
    the head run the lane's LUT, and only the first conv reads codes
    that every lane shares.  A layer name is a per-layer (Fig. 4)
    program: that layer alone runs the LUT, on codes every lane shares,
    and the rest run the exact int8 product."""
    shapes = conv_shapes(cfg, batch)
    if layer is not None:
        return [(layer, *shapes[layer], True)]
    out = [(name, m, k, n, i == 0)
           for i, (name, (m, k, n)) in enumerate(shapes.items())]
    return out + [("head", *head_shape(cfg, batch), False)]
