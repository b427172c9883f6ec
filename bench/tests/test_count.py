"""The counts of ``bench/count.py`` and of the ResNet family
(``bench/counts/resnet_cifar.py``) against the program's own MAC
accounting, and against what the harness counted for every existing
cell before the counts moved to the family (``data/parent_counts.json``):
a cell's counts do not move."""
import json
import os

import pytest

from bench import cells, count
from bench.cells import ROOT
from bench.counts import resnet_cifar
from bench.run import lut_calls

PARENT = os.path.join(os.path.dirname(__file__), "data",
                      "parent_counts.json")


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _parent():
    with open(PARENT) as f:
        return json.load(f)


@pytest.mark.parametrize("name,macs", [("resnet8_cifar", 12_501_632),
                                       ("resnet50_cifar", 111_592_064)])
def test_counts_match_the_program(name, macs):
    from repro.models.resnet import layer_mult_counts, resnet_config

    cfg = _cfg(name)
    rcfg = resnet_config(cfg["depth"])
    shapes = resnet_cifar.conv_shapes(cfg, batch=64)
    assert {l: m * k * n for l, (m, k, n) in shapes.items()} == \
        layer_mult_counts(rcfg, batch=64)
    assert len(shapes) == cfg["approximable_layers"]
    assert resnet_cifar.macs_per_item(cfg) == macs
    assert count.family(cfg).macs_per_item(cfg) == \
        _parent()["macs_per_image"][name]


@pytest.mark.parametrize("name", ["resnet8_cifar.table2",
                                  "resnet50_cifar.table2",
                                  "resnet8_cifar.fig4"])
def test_lut_calls_of_existing_cells_do_not_move(name):
    cell = cells.Cell(name)
    layers = list(resnet_cifar.conv_shapes(cell.config))
    programs = [None] + (layers if cell.traffic["per_layer"] else [])
    want = [tuple(c) for c in _parent()["lut_calls_one_bank"][name]]
    assert lut_calls(cell.config, cell.traffic, programs, 1) == want
    assert lut_calls(cell.config, cell.traffic, programs, 3) == want * 3
    assert count.family(cell.config).eval_items(cell.config,
                                                cell.traffic) == \
        cell.traffic["eval_images"]


def test_lut_calls_on_lane_shards():
    """A bank split over chips: each chip launches every matmul for its
    own lanes, and reads the shared codes itself."""
    cell = cells.Cell("resnet8_cifar.table2")
    whole = lut_calls(cell.config, cell.traffic, [None], 1)
    split = lut_calls(cell.config, {**cell.traffic, "bank_lanes": 76,
                                    "lane_shards": 4}, [None], 2)
    assert split == whole * 8       # 4 chips of 19 lanes, two banks
    launches = resnet_cifar.program_lut_matmuls(cell.config, cell.traffic,
                                                None)
    _, m, k, n, shared = launches[0]
    assert shared and split[0] == count.lut_matmul_work(m, k, n, True, 19)


def test_lut_matmul_work():
    ops, nbytes = count.lut_matmul_work(4096, 576, 64, lanes=2)
    assert ops == 2 * 2 * 4096 * 576 * 64
    assert nbytes == 2 * 4096 * 576 + 2 * (576 * 64 + 256 * 256 * 4
                                           + 4 * 4096 * 64)
    _, shared = count.lut_matmul_work(4096, 576, 64, True, lanes=2)
    assert nbytes - shared == 4096 * 576


def test_program_lut_matmuls():
    cfg = _cfg("resnet8_cifar")
    traffic = {"eval_images": 128, "eval_batch": 64}
    full = resnet_cifar.program_lut_matmuls(cfg, traffic, None)
    assert len(full) == 20 and full[:10] == full[10:]
    assert [c[0] for c in full][9] == "head"
    assert [c[4] for c in full[:10]] == [True] + [False] * 9
    one = resnet_cifar.program_lut_matmuls(cfg, traffic, "s1_b0_conv2")
    assert one == [("s1_b0_conv2", 64 * 16 * 16, 288, 32, True)] * 2
