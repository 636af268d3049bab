"""A run of a small Cholesky cell on the CPU, sound or with one fault planted
under the timed path, for ``test_correct.py``. Cases on the 2x2 grid need
four devices and run in a child process:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m bench.tests.cases <fault> <nb> <pr> <pc>

prints the run's result line.
"""

from __future__ import annotations

import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp

from bench import registry, run
from bench.families import cholesky as fam

B, RANK = 128, 64
# The limit at these sizes, set from CPU readings as a cell's limit is set
# from chip readings: the program reads 1.6e-7 (N = 512) and 2.4e-7
# (N = 768), its control (the reference in bf16_3x) 2.6e-6 and 2.9e-6.
LIMIT = 8e-7
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small_cell(nb: int, pr: int, pc: int, directory: Path) -> dict:
    config = {**json.loads(
        (registry.BENCH / "configs/cholesky_f32_b1024.json").read_text()),
        "nb": nb, "b": B, "grid": [pr, pc], "spd_rank": RANK,
        "limits": {"l_err": LIMIT, "solves_differing": 0}}
    path = directory / "small.json"
    path.write_text(json.dumps(config))
    spec = registry.benchmark()
    spec["configs"] = [{**spec["configs"][0], "file": str(path)}]
    spec["workloads"] = [{**spec["workloads"][0], "chips": pr * pc,
                          "config": spec["configs"][0]["name"]}]
    return registry.cell(spec["workloads"][0]["name"], spec)


@contextlib.contextmanager
def fault(name: str, nb: int):
    """Break the timed path underneath the harness."""
    real = fam.cholesky_executor
    if name == "sound":
        yield
    elif name == "state_unchanged":       # the executor hands its input back
        with mock.patch.object(fam, "cholesky_executor",
                               lambda prog, mesh, **kw: lambda x: x):
            yield
    elif name == "updates_dropped":       # trailing updates subtract nothing
        with mock.patch.object(
                fam, "task_matmul",
                lambda a, b: jnp.zeros((a.shape[0], b.shape[1]), a.dtype)):
            yield
    elif name == "exchange_dropped":      # nothing crosses between chips
        with mock.patch.object(jax.lax, "ppermute",
                               lambda x, *a, **k: jnp.zeros_like(x)), \
                mock.patch.object(jax.lax, "all_to_all",
                                  lambda x, *a, **k: jnp.zeros_like(x)):
            yield
    elif name == "answer_altered":        # one factor entry off by 1e-4
        def altered(prog, mesh, **kw):
            ex = real(prog, mesh, **kw)
            s, slot = prog.slot_of[("L", nb - 1, 0)]
            return lambda x: ex(x).at[s, slot, 0, 0].add(1e-4)

        with mock.patch.object(fam, "cholesky_executor", altered):
            yield
    else:
        raise ValueError(f"no fault {name!r}")


def run_small(name: str, nb: int, pr: int, pc: int, seed: int = 2**31 + 7,
              seconds: float = 0.2) -> dict:
    with tempfile.TemporaryDirectory() as d:
        cell = small_cell(nb, pr, pc, Path(d))
        with fault(name, nb):
            return run.run(cell, seed, seconds, False,
                           jax.devices()[: pr * pc], PEAKS,
                           time.perf_counter())


if __name__ == "__main__":
    fault_name, *sizes = sys.argv[1:]
    print(json.dumps(run_small(fault_name, *map(int, sizes))))
