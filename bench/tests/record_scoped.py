"""Records the small chip traces that ``test_scopes.py`` reads, with the
program's scopes and counters in them.

    python bench/tests/record_scoped.py [--chips 1|4]

Runs a small Cholesky cell (nb 4, b 1024, on one chip or a 2x2 grid)
through ``bench/run.py``'s own ``run`` with ``--trace 1``, for a window of
two or three solves, twice: in the unrolled lowering the cell takes at nb 4,
and in a scan lowering (``unroll_cap`` 2; on 2x2 the union-cover scan).
Keeps each trace (gzipped) and result line under ``bench/tests/data/`` as
``chol_nb4_<chips>chip_<lowering>.*``, the result with the program's
counters as the lowering set them (``program``). Needs the chip; the tests
that read what it wrote do not.
"""

from __future__ import annotations

import argparse
import functools
import gzip
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from repro.core import trace  # noqa: E402
from repro.linalg.cholesky import cholesky_executor  # noqa: E402

from bench import registry, run  # noqa: E402
from bench.device import guard  # noqa: E402
from bench.families import cholesky as fam  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
UNROLL_CAP = {"unrolled": 64, "scan": 2}


def record(chips: int, lowering: str, devices, peaks) -> dict:
    name = "chol_n28k" if chips == 1 else "chol_n32k_2x2"
    cell = registry.cell(name)
    cell["config_data"] = {**cell["config_data"], "nb": 4,
                           "limits": {"l_err": 1.0, "solves_differing": 0}}
    trace.reset()
    executor = functools.partial(cholesky_executor,
                                 unroll_cap=UNROLL_CAP[lowering])
    with mock.patch.object(fam, "cholesky_executor", executor):
        result = run.run(cell, 1, 0.01, True, devices[:chips], peaks)
    result["program"] = trace.snapshot()["gauges"]
    tag = f"chol_nb4_{chips}chip_{lowering}"
    DATA.mkdir(exist_ok=True)
    pb = next(run.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    with gzip.open(DATA / f"{tag}.xplane.pb.gz", "wb") as f:
        f.write(pb.read_bytes())
    (DATA / f"{tag}.result.json").write_text(json.dumps(result, indent=1))
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    run.use_compile_cache()
    devices = jax.devices()
    peaks = guard(devices, args.chips)
    for lowering in UNROLL_CAP:
        print(json.dumps(record(args.chips, lowering, devices, peaks)),
              flush=True)


if __name__ == "__main__":
    main()
