"""Records the small chip trace that ``test_devtrace.py`` reduces.

    python bench/tests/record_trace.py [--chips 1|4]

Runs a small Cholesky cell (nb 4, b 1024, on one chip or a 2x2 grid) through
``bench/run.py``'s own ``run`` with ``--trace 1`` for a window of two or
three solves, and keeps the trace (gzipped) and the result line under
``bench/tests/data/``. Needs the chip; the tests that read what it wrote do
not.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import registry, run  # noqa: E402
from bench.device import guard  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    run.use_compile_cache()
    name = "chol_n28k" if args.chips == 1 else "chol_n32k_2x2"
    cell = registry.cell(name)
    cell["config_data"] = {**cell["config_data"], "nb": 4,
                           "limits": {"l_err": 1.0, "solves_differing": 0}}
    devices = jax.devices()
    peaks = guard(devices, args.chips)
    result = run.run(cell, 1, 0.01, True, devices[: args.chips], peaks)
    tag = f"chol_nb4_{args.chips}chip"
    DATA.mkdir(exist_ok=True)
    pb = next(run.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    with gzip.open(DATA / f"{tag}.xplane.pb.gz", "wb") as f:
        f.write(pb.read_bytes())
    (DATA / f"{tag}.result.json").write_text(json.dumps(result, indent=1))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
