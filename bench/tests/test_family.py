"""A configuration of another family is new files alone: in a copy of
the benchmark (``BENCHMARK.json`` and ``bench/``), a two-layer dense
network is added as a configuration, a family's model, reference and
counts, a traffic mix and limits, with entries in ``BENCHMARK.json``;
no file of the copy is edited.  A whole run of its cell, with the look
for a chip skipped, is correct, and its counts reach the per-layer
readers."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench.cells import ROOT

FAMILY = "dense2"
CELL = "dense2_tiny.rows"

FILES = {
    "configs/dense2_tiny.json": {
        "family": FAMILY, "d_in": 16, "d_hidden": 32, "n_classes": 8,
        "weights": {"key": 5}},
    "traffic/rows_8.json": {
        "generator": "sweep", "library": "tiny",
        "multiplier_prefix": "mul8u_", "bank_lanes": 2, "mode": "lut",
        "variant": "ref", "per_layer": False, "quality_bound": 0.01,
        "eval_rows": 8, "eval_batch": 4, "check_rows": 2},
    f"limits/{CELL}.json": {"limits": {"logit_mae_gap": 0.001}},
    f"models/{FAMILY}.py": '''
        """Two dense layers through the program's ``logit_fidelity``."""
        import jax
        import jax.numpy as jnp


        def make_params(cfg, root):
            def init():
                k1, k2 = jax.random.split(jax.random.PRNGKey(
                    cfg["weights"]["key"]))
                return {"fc1": jax.random.normal(
                            k1, (cfg["d_in"], cfg["d_hidden"])),
                        "fc2": jax.random.normal(
                            k2, (cfg["d_hidden"], cfg["n_classes"]))}
            return jax.jit(init)()


        def inputs(cfg, traffic):
            x = jax.random.normal(jax.random.PRNGKey(11),
                                  (traffic["eval_rows"], cfg["d_in"]))
            return x.reshape(-1, traffic["eval_batch"], cfg["d_in"])


        def program_workload(cfg, params, traffic):
            from repro.approx.layers import dense
            from repro.approx.workload import logit_fidelity

            def forward(policy, x):
                h = jax.nn.relu(dense(policy, "fc1", x, params["fc1"]))
                return dense(policy, "fc2", h, params["fc2"])
            b = traffic["eval_batch"]
            counts = {"fc1": b * cfg["d_in"] * cfg["d_hidden"],
                      "fc2": b * cfg["d_hidden"] * cfg["n_classes"]}
            return logit_fidelity(forward, list(inputs(cfg, traffic)),
                                  layer_counts=counts)
        ''',
    f"references/{FAMILY}.py": '''
        """Plain reference of the two dense layers: the ResNet
        reference's quantized matmul, float32 logits as the baseline."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from bench.models.dense2 import inputs
        from bench.netlist import exact_lut
        from bench.references.resnet_cifar import _matmul


        class Reference:
            def __init__(self, cfg, params, traffic, dtype=jnp.float32):
                self.params, self.dtype = params, dtype
                self.x = inputs(cfg, traffic)
                with jax.default_matmul_precision("highest"):
                    self.golden = [jax.nn.relu(x @ params["fc1"])
                                   @ params["fc2"] for x in self.x]

            def logits(self, lut, layer, x):
                luts = {l: (lut, True) if layer in ("all", l)
                        else (exact_lut(), False) for l in ("fc1", "fc2")}
                with jax.default_matmul_precision("highest"):
                    h = jax.nn.relu(_matmul(x, self.params["fc1"],
                                            *luts["fc1"], self.dtype))
                    return _matmul(h, self.params["fc2"], *luts["fc2"],
                                   self.dtype).astype(jnp.float32)

            def row(self, lut, layer):
                lut = jnp.asarray(lut)
                maes = [jnp.mean(jnp.abs(self.logits(lut, layer, x) - g))
                        for x, g in zip(self.x, self.golden)]
                return {"logit_mae": float(np.mean(maes))}

            def logit_scale(self):
                return float(np.mean([jnp.mean(jnp.abs(g))
                                      for g in self.golden]))
        ''',
    f"counts/{FAMILY}.py": '''
        """Counts of the two dense layers."""


        def macs_per_item(cfg):
            return cfg["d_hidden"] * (cfg["d_in"] + cfg["n_classes"])


        def eval_items(cfg, traffic):
            return traffic["eval_rows"]


        def program_lut_matmuls(cfg, traffic, layer):
            b = traffic["eval_batch"]
            mms = [("fc1", b, cfg["d_in"], cfg["d_hidden"], True),
                   ("fc2", b, cfg["d_hidden"], cfg["n_classes"], False)]
            if layer is not None:
                mms = [(l, m, k, n, True) for l, m, k, n, _ in mms
                       if l == layer]
            return mms * (traffic["eval_rows"] // b)
        ''',
}

RUN_CELL = '''
import json
import sys

import jax
from bench import cells, device, run
v5e = device.peaks("TPU v5 lite")
device.peaks = lambda kind: v5e
cell = cells.Cell(sys.argv[1])
out = run.run_cell(cell, jax.devices()[:1], 3_000_000_031, 0.0, False)
from bench.count import family
out["macs_per_item"] = family(cell.config).macs_per_item(cell.config)
out["lut_calls"] = run.lut_calls(cell.config, cell.traffic, [None], 1)
print(json.dumps(out))
'''


def _add_family(copy: str) -> None:
    for rel, body in FILES.items():
        path = os.path.join(copy, "bench", rel)
        assert not os.path.exists(path), rel
        with open(path, "w") as f:
            if isinstance(body, dict):
                json.dump(body, f)
            else:
                f.write(textwrap.dedent(body).lstrip())
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dense2_tiny", "source": "a test family",
        "file": "bench/configs/dense2_tiny.json", "reduced": [],
        "why": "two dense layers"})
    bench["workloads"].append({
        "name": CELL, "config": "dense2_tiny", "traffic": "rows_8",
        "chips": 1, "why": "a second family"})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_second_family_from_new_files(tmp_path):
    copy = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(copy, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    before = {os.path.relpath(os.path.join(d, f), copy)
              for d, _, fs in os.walk(copy) for f in fs}
    _add_family(copy)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([copy, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", RUN_CELL, CELL], cwd=copy,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["attempted"] == 2
    assert out["macs_per_item"] == 32 * (16 + 8)
    assert [tuple(c) for c in out["lut_calls"]] == [
        (2 * 4 * 16 * 32 * 2, 4 * 16 + 2 * (16 * 32 + 256 * 256 * 4
                                            + 4 * 4 * 32)),
        (2 * 4 * 32 * 8 * 2, 2 * (4 * 32 + 32 * 8 + 256 * 256 * 4
                                  + 4 * 4 * 8))] * 2
    # nothing that was there was edited but BENCHMARK.json's lists
    for rel in before - {"BENCHMARK.json"}:
        with open(os.path.join(ROOT, rel), "rb") as a, \
                open(os.path.join(copy, rel), "rb") as b:
            assert a.read() == b.read(), rel
