"""``BENCHMARK.json`` and the files the harness finds by name."""
import os
import re
import subprocess
import sys

import pytest

from bench import cells
from bench.cells import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert len(BENCH["command"]) <= 32
    assert BENCH["command"][1].startswith("bench/")


def test_entry_keys_and_text():
    for group, keys in KEYS.items():
        for entry in BENCH[group]:
            assert set(entry) - {"workloads"} == keys, entry
            for k in ("why", "layer", "source"):
                if k in entry:
                    assert 1 <= len(entry[k]) <= 200, entry[k]
                    assert "\n" not in entry[k] and "\t" not in entry[k]
            if group in ("end_to_end", "per_layer"):
                assert entry["better"] in ("lower", "higher")
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/configs/")
        assert len(c["reduced"]) <= 16


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = cells.Cell(name)
    assert cell.config["family"] and cell.traffic["generator"]
    for f in (f"bench/models/{cell.config['family']}.py",
              f"bench/references/{cell.config['family']}.py",
              f"bench/counts/{cell.config['family']}.py",
              f"bench/generators/{cell.traffic['generator']}.py"):
        assert os.path.exists(os.path.join(ROOT, f)), f
    assert cell.traffic.get("lane_shards", 1) <= cell.chips
    assert set(cell.limits) == {"logit_mae_gap"}
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_metric_cell_lists_name_reporting_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            assert w in CELLS
            assert cells.applies(e2e[m["moves"]], w)
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", m["name"] + ".py"))


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
            for key in ("config", "traffic"):
                if key in entry:
                    assert NAME.match(entry[key])
    for cfg in BENCH["configs"]:
        assert all(NAME.match(k) for k in cfg["reduced"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()
