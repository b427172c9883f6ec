"""The comparison that decides ``correct``.

Once the window has closed, a sample of the rows it completed, drawn
from the seed with an all-layers row (the longest) among them, is
evaluated again by the configuration's plain reference
(``bench/references/<family>.py``), and one number is compared with the
limit of ``bench/limits/<cell>.json``: ``logit_mae_gap``, the largest
gap between a sampled row's ``logit_mae`` and the reference's, over the
mean absolute logit of the reference's exact datapath.

The rows' accuracy is not compared: it is a step function of the same
logits, so a near-tie could flip an image on float rounding alone, and
a limit of one image would not reject the control on every seed.  The
limit, the readings it was set from and the control it rejects are in
``PERF.md``.
"""
from __future__ import annotations

import numpy as np


def sample(rows: list, n: int, seed: int) -> list[int]:
    """Indices of ``min(n, len(rows))`` distinct rows drawn from
    ``seed``, the first of them an all-layers row."""
    rng = np.random.default_rng([seed, 13])
    full = [i for i, r in enumerate(rows) if r[1] == "all"]
    first = int(rng.choice(full))
    rest = [i for i in range(len(rows)) if i != first]
    k = min(n - 1, len(rest))
    return [first] + [int(i) for i in rng.choice(rest, size=k,
                                                  replace=False)]


def compare(rows: list, picks: list[int], reference, lut_of
            ) -> dict[str, float]:
    """The compared numbers of the picked rows.  ``lut_of(name)`` gives
    a multiplier's product table as the reference computes it."""
    scale = reference.logit_scale()
    gap = 0.0
    for i in picks:
        mult, layer, got = rows[i]
        want = reference.row(lut_of(mult), layer)
        gap = max(gap, abs(got["logit_mae"] - want["logit_mae"]) / scale)
    return {"logit_mae_gap": gap}


def judge(numbers: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, dict]:
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
