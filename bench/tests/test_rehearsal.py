"""CPU rehearsals of whole runs at a tiny size (Pallas in interpret
mode), with the look for a chip skipped: a sound run reproduces the
reference, a run whose timed path is broken underneath is not correct,
and the lower-precision control fails the comparison."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, correct, device
from bench import run as bench_run
from bench.references import resnet_cifar as ref

#: a seed above 32 signed bits, as the driver's are
SEED = 3_000_000_019
TINY = dict(eval_images=4, eval_batch=2, bank_lanes=2, check_rows=2)


def tiny(name, **over):
    cell = cells.Cell(name)
    cell.traffic = {**cell.traffic, **TINY, **over}
    return cell


@pytest.fixture
def cpu(monkeypatch):
    v5e = device.peaks("TPU v5 lite")
    monkeypatch.setattr(device, "peaks", lambda kind: v5e)
    return jax.devices()[:1]


def test_sound_run_is_correct(cpu):
    out = bench_run.run_cell(tiny("resnet8_cifar.table2"), cpu, SEED, 0.0,
                             False)
    assert out["correct"], out["checks"]
    assert out["attempted"] == TINY["bank_lanes"]
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_sound_per_layer_run_is_correct(cpu):
    out = bench_run.run_cell(tiny("resnet8_cifar.fig4", eval_images=2),
                             cpu, SEED + 1, 0.0, False)
    assert out["correct"], out["checks"]
    assert out["attempted"] == TINY["bank_lanes"] * 10


def _half_batch(monkeypatch):
    """Half of each eval batch left out: the logits of the rest stand
    in for it, so every mean is taken over the rest."""
    from repro.models import resnet
    real = resnet.forward

    def forward(params, images, cfg, policy=resnet.EXACT_POLICY):
        half = real(params, images[:images.shape[0] // 2], cfg, policy)
        return jnp.concatenate([half, half])
    monkeypatch.setattr(resnet, "forward", forward)


def _answer_altered(monkeypatch):
    """One product sum of every banked LUT matmul altered where the
    kernel produces it."""
    from repro.kernels import ops
    real = ops.approx_matmul_lut_bank

    def bank(qa, qw, luts):
        return real(qa, qw, luts).at[:, 0, 0].add(1 << 12)
    monkeypatch.setattr(ops, "approx_matmul_lut_bank", bank)


def _lanes_swapped(monkeypatch):
    """Each lane's answer reported under its neighbour's multiplier."""
    from repro.approx import resilience
    real = resilience._unstack_metrics

    def unstack(out, names, n):
        lanes = real(out, names, n)
        return lanes[1:] + lanes[:1]
    monkeypatch.setattr(resilience, "_unstack_metrics", unstack)


@pytest.mark.parametrize("fault", [_half_batch, _answer_altered,
                                   _lanes_swapped])
def test_broken_run_is_not_correct(cpu, monkeypatch, fault):
    fault(monkeypatch)
    out = bench_run.run_cell(tiny("resnet8_cifar.table2", check_rows=3),
                             cpu, SEED, 0.0, False)
    assert not out["correct"], out["checks"]


def test_control_fails_the_comparison():
    """The reference computed in bfloat16, put in the program's place,
    on the sample a run draws: its numbers exceed the limits."""
    cell = tiny("resnet8_cifar.table2", check_rows=4)
    from repro.core.library import build_default_library
    from bench.models.resnet_cifar import make_params
    lib = build_default_library("tiny")
    names = [n for n in lib.entries if n.startswith("mul8u_")]
    params = make_params(cell.config, cells.ROOT)
    t = cell.traffic
    want = ref.Reference(cell.config, params, t["eval_images"],
                         t["eval_batch"])
    control = ref.Reference(cell.config, params, t["eval_images"],
                            t["eval_batch"], dtype=jnp.bfloat16)

    def lut_of(name):
        nl = lib.entry(name).netlist
        return ref.netlist_lut(nl.n_i, nl.funcs, nl.in0, nl.in1, nl.outputs)

    rng = np.random.default_rng(SEED)
    rows = [(n, "all", control.row(lut_of(n), "all"))
            for n in rng.choice(names, 4, replace=False)]
    numbers = correct.compare(rows, correct.sample(rows, 4, SEED), want,
                              lut_of)
    ok, checks = correct.judge(numbers, cell.limits)
    assert not ok, checks


def test_netlist_tables_match_the_library():
    from repro.core.library import build_default_library
    lib = build_default_library("tiny")
    for name in ("mul8u_exact", "mul8u_trunc5", "mul8u_bam_h2_v7",
                 [n for n in lib.entries if n.startswith("mul8u_E")][0]):
        nl = lib.entry(name).netlist
        got = ref.netlist_lut(nl.n_i, nl.funcs, nl.in0, nl.in1, nl.outputs)
        assert np.array_equal(got, lib.lut(name)), name
    assert np.array_equal(ref.exact_lut(), lib.lut("mul8u_exact"))


def test_eval_images_match_the_program():
    from repro.data.synthetic import synthetic_cifar
    for a, b in zip(ref.synthetic_cifar("test", 8),
                    synthetic_cifar("test", 8)):
        assert np.array_equal(a, b)
