"""``correct`` on the CPU at a small size: the sound run passes, and the
control and each fault that a Cholesky cell can have fail it.

The 2x2 cases run in a child process with four host devices, so the forced
device count never reaches this process.
"""

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import pytest

from bench import run
from bench.families import cholesky as fam
from bench.tests import cases

ROOT = Path(__file__).resolve().parents[2]


def run_2x2(fault: str, nb: int = 6) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.cases", fault, str(nb), "2", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_sound_run_is_correct():
    res = cases.run_small("sound", 4, 1, 1)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert res["checks"]["l_err"]["value"] < cases.LIMIT / 2
    assert res["checks"]["solves_differing"]["value"] == 0
    assert set(res["metrics"]) == {"solve_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "updates_dropped",
                                   "answer_altered"])
def test_a_fault_under_the_timed_path_is_not_correct(fault):
    res = cases.run_small(fault, 4, 1, 1)
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
    assert res["checks"]["l_err"]["value"] > cases.LIMIT


@pytest.mark.parametrize("nb", [4, 6])
def test_the_control_is_not_correct(nb):
    """The reference one precision below the configuration's (bf16_3x for
    highest), in the program's place, reads above the limit, and the checks
    that decide a run's ``correct`` refuse it."""
    with tempfile.TemporaryDirectory() as d:
        config = cases.small_cell(nb, 1, 1, Path(d))["config_data"]
    numbers = fam.control(config, jax.devices()[:1], 11)
    assert numbers["l_err"] > 3 * cases.LIMIT
    checks = run.checks_of(numbers, config["limits"])
    assert not all(map(run.passes, checks.values()))


@pytest.mark.parametrize("fault,correct", [("sound", True),
                                           ("exchange_dropped", False),
                                           ("answer_altered", False)])
def test_on_a_2x2_grid(fault, correct):
    res = run_2x2(fault)
    assert res["correct"] is correct
    assert res["device"]["count"] == 4
