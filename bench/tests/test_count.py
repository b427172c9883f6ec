"""The counts of ``bench/count.py`` against the program's own MAC
accounting, for the two configurations' depths."""
import json
import os

import pytest

from bench import count
from bench.cells import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,macs", [("resnet8_cifar", 12_501_632),
                                       ("resnet50_cifar", 111_592_064)])
def test_counts_match_the_program(name, macs):
    from repro.models.resnet import layer_mult_counts, resnet_config

    cfg = _cfg(name)
    rcfg = resnet_config(cfg["depth"])
    shapes = count.conv_shapes(cfg, batch=64)
    assert {l: m * k * n for l, (m, k, n) in shapes.items()} == \
        layer_mult_counts(rcfg, batch=64)
    assert len(shapes) == cfg["approximable_layers"]
    assert count.macs_per_image(cfg) == macs


def test_lut_matmul_work():
    ops, nbytes = count.lut_matmul_work(4096, 576, 64, lanes=2)
    assert ops == 2 * 2 * 4096 * 576 * 64
    assert nbytes == 2 * 4096 * 576 + 2 * (576 * 64 + 256 * 256 * 4
                                           + 4 * 4096 * 64)
    _, shared = count.lut_matmul_work(4096, 576, 64, True, lanes=2)
    assert nbytes - shared == 4096 * 576


def test_program_lut_matmuls():
    cfg = _cfg("resnet8_cifar")
    full = count.program_lut_matmuls(cfg, 64, None)
    assert [c[0] for c in full][-1] == "head" and len(full) == 10
    assert [c[4] for c in full] == [True] + [False] * 9
    one = count.program_lut_matmuls(cfg, 64, "s1_b0_conv2")
    assert one == [("s1_b0_conv2", 64 * 16 * 16, 288, 32, True)]
