"""Rehearsal of the four-chip cell on four virtual CPU devices, in a
process of its own (JAX fixes the device count when it starts):

    python bench/tests/lane_shards_rehearsal.py

At a tiny size (a bank of 4 lanes, one to a device, 2 eval images) it
sweeps two banks with the lanes sharded and unsharded, then drives whole
runs of the cell with the look for a chip skipped: a sound run, and runs
whose timed path is broken underneath.  The last line of standard output
is one JSON object, read by ``test_lane_shards.py``."""
from __future__ import annotations

import contextlib
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

CELL = "resnet8_cifar.table2.ref.x4"
TINY = dict(eval_images=2, eval_batch=2, bank_lanes=4, check_rows=4)
#: a seed above 32 signed bits
SEED = 3_000_000_023


@contextlib.contextmanager
def patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    try:
        yield
    finally:
        setattr(module, name, real)


def half_batch():
    """Half of each eval batch left out: the logits of the rest stand
    in for it, so every mean is taken over the rest."""
    from repro.models import resnet

    def make(real):
        def forward(params, images, cfg, policy=resnet.EXACT_POLICY):
            half = real(params, images[:images.shape[0] // 2], cfg, policy)
            return jnp.concatenate([half, half])
        return forward
    return patched(resnet, "forward", make)


def answer_altered():
    """One product sum of every row block altered where the row-gather
    datapath produces it."""
    from repro.approx import registry

    def make(real):
        return lambda qa, cols: real(qa, cols).at[0, 0].add(1 << 12)
    return patched(registry, "_lut_gather_block", make)


def exchange_left_out():
    """The rows of the first device's lanes stand in for every device's:
    what the other devices computed never reaches the host."""
    from repro.approx import resilience

    def make(real):
        def unstack(out, names, n):
            first = {m: np.asarray(out[m].addressable_shards[0].data)
                     for m in names}
            return real({m: np.resize(v, n) for m, v in first.items()},
                        names, n)
        return unstack
    return patched(resilience, "_unstack_metrics", make)


FAULTS = {"half_batch": half_batch, "answer_altered": answer_altered,
          "exchange_left_out": exchange_left_out}


def lanes_per_device():
    """``_unstack_metrics`` recording the lanes each device held."""
    from repro.approx import resilience
    seen = []

    def make(real):
        def unstack(out, names, n):
            seen.append({str(s.device.id): int(s.data.shape[0])
                         for s in out[names[0]].addressable_shards})
            return real(out, names, n)
        return unstack
    return patched(resilience, "_unstack_metrics", make), seen


def sweep_rows(cell, shards: int):
    from bench.generators.sweep import Sweep

    sweep = Sweep(cell.config, {**cell.traffic, "lane_shards": shards},
                  SEED, ROOT)
    sweep.setup()
    rows = sweep.run_bank(1) + sweep.run_bank(2)
    sweep.close()
    return rows


def main() -> int:
    from bench import cells, device
    from bench import run as bench_run

    v5e = device.peaks("TPU v5 lite")
    device.peaks = lambda kind: v5e
    cell = cells.Cell(CELL)
    cell.traffic = {**cell.traffic, **TINY}
    devices = jax.devices()[:cell.chips]
    out = {"devices": len(jax.devices())}

    recording, seen = lanes_per_device()
    with recording:
        sharded = sweep_rows(cell, cell.traffic["lane_shards"])
    out["lanes_per_device"] = seen[-1]
    out["rows_equal"] = sharded == sweep_rows(cell, 1)
    out["rows"] = len(sharded)

    runs = {"sound": contextlib.nullcontext}
    runs.update(FAULTS)
    for name, fault in runs.items():
        with fault():
            res = bench_run.run_cell(cell, devices, SEED, 0.0, False)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "count": res["device"]["count"]}
        print(f"[rehearsal] {name}: {out[name]}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
