"""Readings that set a cell's limits; run on the chip, never by a run of
the benchmark.

    python bench/control.py --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3

For each of ``--seeds`` it builds the cell, solves once through the timed
path and prints the numbers ``check`` compares (the lower readings). For
each of ``--control-seeds`` it prints the same numbers with the family's
control, the reference one precision below the configuration's, in the
program's place (the upper readings). Each reading goes through the checks
that decide a run's ``correct``, against the cell's own limits, and its line
gives the verdict: ``correct`` has to come out false for every control
reading. One JSON line per reading, all in one process, so the compile and
the chip's start-up are paid once. The control runs on the cell's first
chip alone, so control readings alone need one chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import registry  # noqa: E402
from bench.device import DeviceError, describe, guard  # noqa: E402
from bench.run import checks_of, passes, use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload)
    use_compile_cache()
    devices = jax.devices()
    chips = cell["chips"] if args.seeds else 1
    try:
        guard(devices, chips)
    except DeviceError as e:
        print(f"control: {e} {describe(devices)}", file=sys.stderr)
        return 1
    devices = devices[:chips]
    config = cell["config_data"]
    family = registry.family(config["family"])

    def report(reading: str, seed: int, numbers: dict, t0: float):
        checks = checks_of(numbers, config["limits"])
        print(json.dumps({"reading": reading, "seed": seed,
                          "correct": all(map(passes, checks.values())),
                          "checks": checks, "s": time.perf_counter() - t0,
                          "device": describe(devices)}), flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        built = family.Cell(config, devices, seed)
        built.solve().block_until_ready()
        report("program", seed, built.check(), t0)
        del built
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        report("control", seed, family.control(config, devices, seed), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
