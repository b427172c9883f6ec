"""Work a sweep needs, counted from a configuration's shapes.

Nothing here reads the program, so that a change to the program cannot
change the yardstick.  What depends on the model lives with its family,
in ``bench/counts/<family>.py`` (``family``); what a LUT matmul costs
does not:

* ``lut_matmul_work``: operations and bytes of one LUT matmul, the work
  it needs whatever implements it: ``2*M*K*N`` operations; uint8 codes
  and weights, the lane's 256x256 int32 LUT and int32 outputs.
"""
from __future__ import annotations

import importlib

LUT_BYTES = 256 * 256 * 4


def family(cfg: dict):
    """The counts of the configuration's family: the module
    ``bench.counts.<cfg["family"]>`` (see ``bench/counts/__init__.py``)."""
    return importlib.import_module(f"bench.counts.{cfg['family']}")


def lut_matmul_work(m: int, k: int, n: int, shared_codes: bool = False,
                    lanes: int = 1) -> tuple[int, int]:
    """(operations, bytes) of ``lanes`` LUT matmuls of one (M, K, N)
    shape.  Codes shared by every lane are read once."""
    ops = 2 * m * k * n * lanes
    codes = m * k * (1 if shared_codes else lanes)
    per_lane = k * n + LUT_BYTES + 4 * m * n
    return ops, codes + per_lane * lanes
