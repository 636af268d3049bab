"""chip_smoke.py on the CPU: its phases at a tiny size (Pallas bodies in
interpret mode), and its refusal to report a result without a TPU or
outside the repository. The four-chip phase runs on forced host devices in
``multi_device_cases.case_chip_smoke_four_chips``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import jax

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def test_cholesky_phase_within_bounds(highest_precision):
    f = chip_smoke.cholesky_phase(jax.devices()[0], 3, 128, seed=0)
    assert f["N"] == 384 and f["wavefronts"] > 1
    assert f["residual"] <= f["residual_bound"], f
    assert f["ref_err"] <= f["ref_bound"], f


def test_gemm_phase_within_bound(highest_precision):
    f = chip_smoke.gemm_phase(jax.devices()[0], 3, 128, seed=0)
    assert f["err_over_abs_ab"] <= f["bound"], f


def test_stream_phase_bit_identical_to_run_host():
    # the host runtime's worker threads do not inherit a context-local
    # precision, so this phase runs at the default one throughout
    f = chip_smoke.stream_phase(jax.devices()[0], 3, 128, seed=0)
    assert f["submissions"] == 4
    assert f["stream_bit_identical_to_run_host"], f
    assert f["host_vs_compiled"] <= f["bound"], f


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_no_result_without_a_tpu_or_outside_the_repo(where, tmp_path):
    """Without a TPU, and from a directory holding only the script, the run
    exits non-zero and never prints the ``ok`` line."""
    cwd = REPO
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    proc = _run("chip_smoke.py", cwd)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert "ok" not in json.loads(line), line
