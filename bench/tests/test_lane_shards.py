"""A four-chip cell on four virtual CPU devices
(``lane_shards_rehearsal.py``, run once in a process of its own): the
lanes of a bank split one to a device, the rows equal the unsharded
sweep's, a sound run is correct, and a run whose timed path is broken
underneath is not: half of each batch left out, an answer altered where
the datapath produces it, the other devices' rows never gathered."""
import json
import os
import subprocess
import sys

import pytest

from bench.cells import ROOT

SCRIPT = os.path.join(os.path.dirname(__file__), "lane_shards_rehearsal.py")


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_lanes_split_over_the_devices(rehearsal):
    assert rehearsal["devices"] == 4
    assert rehearsal["lanes_per_device"] == {"0": 1, "1": 1, "2": 1, "3": 1}


def test_sharded_rows_equal_unsharded(rehearsal):
    assert rehearsal["rows"] == 8 and rehearsal["rows_equal"]


def test_sound_run_is_correct(rehearsal):
    assert rehearsal["sound"]["correct"], rehearsal["sound"]
    assert rehearsal["sound"]["count"] == 4


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "exchange_left_out"])
def test_broken_run_is_not_correct(rehearsal, fault):
    assert not rehearsal[fault]["correct"], rehearsal[fault]
