#!/usr/bin/env python3
"""Smoke run of the paper's main path on a TPU: the banked resilience
sweep of the trained ResNet-8 (CIFAR widths 16/32/64).

    python chip_smoke.py             # one chip: kernel phase + sweep phase
    python chip_smoke.py --chips 4   # four chips: sharded sweep vs device 0

One chip:

* kernel phase — at every ResNet-8 conv layer's im2col shape (eval
  batch 64), the Pallas 8-bit LUT kernels (single LUT and bank through
  ``ops.approx_matmul_lut`` and its vmap rule; ``fused_matmul_pallas``
  and ``fused_matmul_bank_pallas`` through their ``ops`` wrappers) must
  equal the ``ref.py`` oracles exactly, and both netlist simulators
  ``Netlist.eval_words`` on exhaustive 8x8 multipliers;
* sweep phase — ``explore(..., batch=True)`` with the ``ref``,
  ``pallas`` and ``fused`` datapaths over the library's case-study
  multipliers, on 256 eval images: all-layers and per-layer accuracies
  and the selected point must be bit-identical across the three; to
  the sequential ``ref`` sweep (``batch=False``, one compiled program
  per row) the all-layers rows, the golden baseline, the selected point
  and a diagonal of the per-layer rows — every layer once, every
  multiplier once, 9 of the 81; and the ``mul8u_exact`` lane to the
  golden int8 accuracy.

Four chips: the all-layers sweep over 12 multipliers, on one batch of 64
eval images, with the bank axis sharded 4 ways (``bank_sharding``) must
give the rows of the same sweep on device 0, and each device must hold
3 lanes of the LUT bank and of the banked program's output.

Inputs come from committed files only: the library is built from its
seed (``build_default_library("tiny")``) and the model is restored from
``benchmarks/results/resnet8_ckpt_v2``.  Everything runs in this one
process; the sweeps of the sweep phase run side by side in threads of
it, so that their programs compile in parallel.  Wall and compile times are printed per phase; they are those
of a smoke run, not measurements.  Any failed check raises, so the
process exits non-zero; the last line of stdout is then not the
``{"ok": true, ...}`` JSON object it prints on success.  Without a TPU
it exits with code 1 before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
EVAL_N = 256
EVAL_BATCH = 64
QUALITY_BOUND = 0.01
SHARDED_LANES = 12
VARIANTS = ("ref", "pallas", "fused")
#: eval images of the four-chip phase: one batch — it checks the sharded
#: program against device 0; the one-chip sweep phase uses all 256
SHARDED_EVAL_N = 64
#: LUT bank lanes of the kernel phase: every lane is checked against the
#: flat-gather oracle, which is slow on a TPU; the sweep phase runs the
#: whole bank
KERNEL_LANES = 3
#: sweeps run side by side: XLA compiles without holding the GIL, and
#: compiling ~50 programs one after another would take most of the
#: 1200 s limit; the chip runs one program at a time either way
SWEEP_THREADS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def conv_shapes(cfg, batch: int) -> dict[str, tuple[int, int, int]]:
    """(M, K, N) im2col matmul shape of every conv layer of a CIFAR
    ResNet at ``batch`` images (32x32 inputs, stride 2 entering each
    stage after the first)."""
    side = 32
    shapes = {"conv_init": (batch * side * side, 9 * 3, cfg.widths[0])}
    cin = cfg.widths[0]
    for s, width in enumerate(cfg.widths):
        for b in range(cfg.n_blocks):
            if s > 0 and b == 0:
                side //= 2
            m = batch * side * side
            name = f"s{s}_b{b}"
            shapes[f"{name}_conv1"] = (m, 9 * cin, width)
            shapes[f"{name}_conv2"] = (m, 9 * width, width)
            if cin != width:
                shapes[f"{name}_proj"] = (m, cin, width)
            cin = width
    return shapes


class Timer:
    """Wall time and XLA compile time of one phase."""

    def __init__(self, label: str):
        from repro.launch.compile_cache import trace_audit
        self.label = label
        self._audit = trace_audit()

    def __enter__(self):
        self.counts = self._audit.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        self._audit.__exit__(*exc)
        if exc[0] is None:
            log(f"[smoke time] {self.label}: wall {wall:.1f} s, "
                f"{self.counts.compiles} programs compiled in "
                f"{self.counts.compile_secs:.1f} s")
        return False


def kernel_phase(lib, names, cfg) -> None:
    """Every brought-up 8-bit Pallas kernel against its ``ref.py``
    oracle at each conv layer's im2col shape, exactly; then the
    netlist-simulation kernels."""
    import jax.numpy as jnp
    import numpy as np

    from repro.approx.quant import calibrate, scalar_params
    from repro.core import families, seeds
    from repro.core.netlist import exhaustive_inputs
    from repro.kernels import ops, ref
    from repro.models import resnet

    shapes = conv_shapes(cfg, EVAL_BATCH)
    counts = resnet.layer_mult_counts(cfg)
    assert {l: m * k * n for l, (m, k, n) in shapes.items()} == \
        {l: c * EVAL_BATCH for l, c in counts.items()}, (shapes, counts)

    luts = jnp.asarray(np.stack([lib.lut(n) for n in names]), jnp.int32)
    n_mult = luts.shape[0]
    lut_ref = jax.jit(ref.approx_matmul_lut_ref)
    fused_ref = jax.jit(ref.fused_matmul_ref)
    single = jax.jit(ops.approx_matmul_lut)
    bank_shared = jax.jit(jax.vmap(ops.approx_matmul_lut,
                                   in_axes=(None, None, 0)))
    bank_banked = jax.jit(jax.vmap(ops.approx_matmul_lut,
                                   in_axes=(0, None, 0)))
    fused = jax.jit(ops.fused_matmul_lut)
    fused_bank = jax.jit(ops.fused_matmul_lut_bank)

    def same(got, want, what):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape, (what, got.shape, want.shape)
        assert np.array_equal(got, want), \
            f"{what}: {int(np.sum(got != want))} of {got.size} differ"

    rng = np.random.default_rng(0)
    seen = set()
    for layer, (m, k, n) in shapes.items():
        if (m, k, n) in seen:
            continue
        seen.add((m, k, n))
        with Timer(f"kernel phase {layer} (M,K,N)=({m},{k},{n})"):
            qa = jnp.asarray(rng.integers(0, 256, (m, k)), jnp.int32)
            qa_b = jnp.asarray(rng.integers(0, 256, (n_mult, m, k)),
                               jnp.int32)
            qw = jnp.asarray(rng.integers(0, 256, (k, n)), jnp.int32)
            want = [lut_ref(qa, qw, luts[i]) for i in range(n_mult)]
            same(single(qa, qw, luts[0]), want[0],
                 f"{layer} approx_matmul_lut")
            got_s = bank_shared(qa, qw, luts)
            got_b = bank_banked(qa_b, qw, luts)
            for i in range(n_mult):
                same(got_s[i], want[i],
                     f"{layer} bank lane {i} (shared codes)")
                same(got_b[i], lut_ref(qa_b[i], qw, luts[i]),
                     f"{layer} bank lane {i} (banked codes)")

            x = jnp.asarray(rng.normal(size=(n_mult, m, k)), jnp.float32)
            x = x * jnp.arange(1, n_mult + 1, dtype=jnp.float32)[:, None,
                                                                 None]
            w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
            sp = [scalar_params(calibrate(x[i]), calibrate(w))
                  for i in range(n_mult)]
            same(fused(x[0], w, luts[0], *sp[0]),
                 fused_ref(x[0], w, luts[0], *sp[0]),
                 f"{layer} fused_matmul_pallas")
            lanes = [jnp.stack(v) for v in zip(*sp)]
            got_f = fused_bank(x, w, luts, *lanes)
            for i in range(n_mult):
                same(got_f[i], fused_ref(x[i], w, luts[i], *sp[i]),
                     f"{layer} fused bank lane {i}")
        log(f"[kernel] {layer} (M,K,N)=({m},{k},{n}): single, bank "
            f"(shared and banked codes, {n_mult} lanes), fused, fused "
            f"bank: exactly equal to ref.py")

    with Timer("kernel phase: bitsim"):
        netlists = [seeds.array_multiplier(8),
                    families.bam_multiplier(8, 1, 4)]
        planes = exhaustive_inputs(16)
        pop = ops.bitsim_pop(netlists, planes)
        for i, nl in enumerate(netlists):
            want = nl.eval_words(planes)
            assert np.array_equal(ops.bitsim(nl, planes), want), i
            assert np.array_equal(pop[i], want), i
    log("[kernel] bitsim_pallas and bitsim_pop_pallas on two exhaustive "
        "8x8 multipliers: equal to Netlist.eval_words")


def _rows(result) -> list[tuple]:
    return [(p.multiplier, p.layer, p.accuracy)
            for p in result.all_layers + result.per_layer]


def _selected(result) -> tuple:
    s = result.selected
    return (s.multiplier, s.layer, s.accuracy, s.network_rel_power)


def sweep_phase(lib, names, cfg, params) -> None:
    """The banked sweep under three datapaths, and the sequential
    sweep of the all-layers rows and a per-layer diagonal; bit-identical
    accuracies and selection."""
    from benchmarks.resilience_common import make_eval_fn
    from repro.approx.dse import explore

    wl = make_eval_fn(cfg, params, eval_n=EVAL_N, batch=EVAL_BATCH)
    cache: dict = {}          # shares the golden baseline across sweeps
    with Timer("sweep phase: golden baseline"):
        explore(wl, wl.layer_counts, lib, multipliers=names,
                all_layers=False, per_layer=False, cache=cache)
    # the sequential sweeps start from the golden baseline alone, not
    # from the batched rows that explore(batch=True) writes into `cache`
    seq_cache = dict(cache)

    def batched(variant):
        return explore(wl, wl.layer_counts, lib, multipliers=names,
                       batch=True, variant=variant,
                       quality_bound=QUALITY_BOUND, cache=cache)

    def sequential_all_layers():
        return explore(wl, wl.layer_counts, lib, multipliers=names,
                       batch=False, variant="ref", per_layer=False,
                       quality_bound=QUALITY_BOUND, cache=seq_cache)

    def sequential_row(layer, mult):
        return explore(wl, {layer: wl.layer_counts[layer]}, lib,
                       multipliers=[mult], batch=False, variant="ref",
                       all_layers=False, cache=seq_cache).per_layer[0]

    # one compiled program per sequential row: all 81 per-layer rows
    # would not fit the time limit, so a diagonal checks every layer and
    # every multiplier once (the exact lane on the last layer)
    diagonal = list(zip(wl.layer_counts, names[1:] + names[:1]))
    with Timer(f"sweep phase, {SWEEP_THREADS} threads: batch=True "
               f"{VARIANTS}; batch=False ref, all layers and "
               f"{len(diagonal)} per-layer rows"), \
            ThreadPoolExecutor(SWEEP_THREADS) as pool:
        jobs = {v: pool.submit(batched, v) for v in VARIANTS}
        seq_job = pool.submit(sequential_all_layers)
        row_jobs = [pool.submit(sequential_row, layer, mult)
                    for layer, mult in diagonal]
        results = {v: job.result() for v, job in jobs.items()}
        seq = seq_job.result()
        seq_layer = [job.result() for job in row_jobs]
    for variant, r in results.items():
        log(f"[sweep] variant={variant}: {len(_rows(r))} rows, golden "
            f"int8 accuracy {r.baseline_accuracy!r}, selected "
            f"{_selected(r)}")
    seq_layer = [(p.multiplier, p.layer, p.accuracy) for p in seq_layer]
    assert [r[:2] for r in seq_layer] == [(m, l) for l, m in diagonal]

    ref_rows = _rows(results["ref"])
    seq_layer_keys = [row[:2] for row in seq_layer]
    mismatches = []
    for variant, r in results.items():
        r_rows = _rows(r)
        for what, got, want in (
                (f"{variant} batched vs ref batched", r_rows, ref_rows),
                (f"{variant} batched vs sequential (all layers)",
                 r_rows[:len(names)], _rows(seq)),
                (f"{variant} batched vs sequential (per-layer diagonal)",
                 [{row[:2]: row for row in r_rows}.get(key)
                  for key in seq_layer_keys], seq_layer)):
            diff = [(g, w) for g, w in zip(got, want) if g != w]
            if diff or len(got) != len(want):
                mismatches.append(what)
                log(f"[sweep] MISMATCH {what}: {len(diff)} of {len(want)} "
                    f"rows differ, e.g. {diff[:3]}")
    assert not mismatches, mismatches
    for variant, r in results.items():
        assert r.baseline_accuracy == seq.baseline_accuracy, variant
        assert _selected(r) == _selected(seq), variant
    exact = [acc for mult, _, acc in ref_rows if mult == "mul8u_exact"]
    assert exact and all(a == seq.baseline_accuracy for a in exact), exact
    log(f"[sweep] {len(ref_rows)} rows bit-identical across ref/pallas/"
        f"fused batched; their {len(names)} all-layers rows, "
        f"{len(seq_layer)} per-layer rows {seq_layer_keys}, golden int8 "
        f"accuracy and selected point bit-identical to the sequential "
        f"sweep; mul8u_exact lane == golden int8 "
        f"({seq.baseline_accuracy!r}) in {len(exact)} rows")


def sharded_phase(lib, cfg, params, n_chips: int) -> None:
    """The all-layers sweep with the bank axis sharded across the chips,
    against the same sweep on device 0."""
    import jax.numpy as jnp

    from benchmarks.resilience_common import case_study_names, make_eval_fn
    from repro.approx.dse import explore
    from repro.approx.layers import bank_eval
    from repro.approx.specs import bank_for
    from repro.launch.mesh import bank_sharding

    assert len(jax.devices()) == n_chips, jax.devices()
    names = case_study_names(lib, SHARDED_LANES)
    names += [e for e in lib.entries
              if e.startswith("mul8u_") and e not in names]
    names = names[:SHARDED_LANES]
    assert len(names) == SHARDED_LANES, names
    sharding = bank_sharding(len(names))
    luts = jax.device_put(
        jnp.stack([jnp.asarray(lib.lut(n), jnp.int32) for n in names]),
        sharding)
    per_device = {s.device.id: s.data.shape[0]
                  for s in luts.addressable_shards}
    assert set(per_device.values()) == {SHARDED_LANES // n_chips}, \
        per_device
    log(f"[sharded] bank of {len(names)} LUTs: lanes per device "
        f"{per_device}")

    wl = make_eval_fn(cfg, params, eval_n=SHARDED_EVAL_N, batch=EVAL_BATCH)
    with Timer(f"banked program on {n_chips} chips"):
        acc = bank_eval(wl.traceable_metrics, bank_for(names, lib),
                        sharding=sharding)["accuracy"]
        out_lanes = {s.device.id: s.data.shape[0]
                     for s in acc.addressable_shards}
    assert out_lanes == per_device, (out_lanes, per_device)
    log(f"[sharded] banked program output lanes per device {out_lanes}")
    with Timer(f"sharded sweep on {n_chips} chips"):
        sharded = explore(wl, wl.layer_counts, lib, multipliers=names,
                          batch=True, sharding=sharding, per_layer=False,
                          quality_bound=QUALITY_BOUND)
    with Timer("same sweep on device 0"):
        single = explore(wl, wl.layer_counts, lib, multipliers=names,
                         batch=True, per_layer=False,
                         quality_bound=QUALITY_BOUND)
    assert _rows(sharded) == _rows(single)
    assert _selected(sharded) == _selected(single)
    log(f"[sharded] {len(_rows(single))} rows and the selected point "
        f"bit-identical between {n_chips} chips and device 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    record = device_record()
    log(f"[device] platform={record['platform']} kind={record['kind']} "
        f"count={record['count']}")
    if record["platform"] != "tpu":
        log("[device] no TPU: the smoke run needs one")
        return 1
    if record["count"] < args.chips:
        log(f"[device] {args.chips} chips asked for, {record['count']} "
            f"found")
        return 1

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[setup] persistent compile cache: {enable_compile_cache()}")

    from benchmarks.resilience_common import (case_study_names,
                                              restore_resnet8)
    from repro.core.library import build_default_library

    with Timer("setup: library from seed + committed checkpoint"):
        lib = build_default_library("tiny")
        cfg, params = restore_resnet8()
    if args.chips == 4:
        sharded_phase(lib, cfg, params, 4)
    else:
        names = case_study_names(lib, 8)
        log(f"[setup] case-study multipliers: {names}")
        kernel_phase(lib, names[:KERNEL_LANES], cfg)
        sweep_phase(lib, names, cfg, params)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
