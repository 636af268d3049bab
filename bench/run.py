"""Runs one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds all of the cell's chips. It builds the cell's inputs on
the device from ``--seed``, compiles and warms up the cell's one shape (the
set-up, ``setup_s``), drives solves for ``--seconds`` under the cell's
traffic, then checks the last solve's output against the plain reference and
every solve's output against the last one's.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, ``setup_phases``
(the set-up's parts, in seconds), and last ``checks``: each number compared
beside its limit, which also close standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

import jax  # noqa: E402

from bench import devtrace, registry  # noqa: E402
from bench.device import (DeviceError, describe, guard,  # noqa: E402
                          matmul_peak)

TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
# a hang fails the run with every thread's stack; the first run of a cell in
# a checkout compiles and may take up to 1200 s
WATCHDOG_S = 1150


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is set
    (JAX reads it), else a fixed directory in the checkout. Every program is
    cached, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    """The fullest chip's peak: its buffers' peak (``peak_bytes_in_use``)
    plus what the runtime holds reserved for the loaded programs'
    temporaries (``bytes_reserved``), which a TPU keeps apart from the
    buffers in use. Read after the window, while the timed program, the
    largest, is still loaded."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("bytes_reserved", 0)) for s in stats)


def checks_of(numbers: dict, limits: dict, fps=None) -> dict:
    """Each number compared beside its limit: the family's ``numbers`` and,
    given the window's output fingerprints ``fps``, ``solves_differing``,
    the solves whose output differs from the checked (last) one's."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    if fps is not None:
        differing = sum(int(not np.array_equal(f, fps[-1])) for f in fps)
        checks["solves_differing"] = {"value": differing,
                                      "limit": limits["solves_differing"]}
    return checks


def passes(check: dict) -> bool:
    return check["limit"] is not None and check["value"] <= check["limit"]


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        peaks: dict, t_start: float = T_START) -> dict:
    """One run of ``cell`` (``registry.cell``) on ``devices``; returns the
    result object, ``checks`` last."""
    config = cell["config_data"]
    family = registry.family(config["family"])
    phases = {"start_s": time.perf_counter() - t_start}
    built = family.Cell(config, devices, seed)
    phases.update(built.phases)
    if trace:
        # started before the warm-up, so the tracer's own start-up stalls
        # no solve of the window
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    try:
        t0 = time.perf_counter()
        built.solve().block_until_ready()                   # warm-up
        phases["warmup_s"] = time.perf_counter() - t0
        setup_s = time.perf_counter() - t_start
        traffic = cell["traffic_data"]
        fps, window_s = registry.loop(traffic)(traffic, built.solve, seconds)
        fps = [np.asarray(f) for f in fps]
    finally:
        if trace:
            jax.profiler.stop_trace()
    device = {**describe(devices), "memory_peak_bytes": memory_peak(devices)}

    checks = checks_of(built.check(), config["limits"], fps)
    ok = {k: passes(c) for k, c in checks.items()}
    attempted = len(fps)
    # a wrong answer is wrong in every solve, whose outputs are all alike
    wrong = not all(v for k, v in ok.items() if k != "solves_differing")
    failed = attempted if wrong else checks["solves_differing"]["value"]

    facts = {"chips": len(devices), "peaks": peaks,
             "matmul_peak": matmul_peak(peaks, config["matmul_precision"]),
             "solves": attempted, "window_s": window_s,
             "solve_s": window_s / attempted, "setup_s": setup_s,
             "host_setup_s": built.host_setup_s, "lower_s": built.lower_s,
             "solve_flops": built.flops(), "trace": None}
    result = {"correct": all(ok.values()), "attempted": attempted,
              "failed": failed}
    if trace:
        reduction = devtrace.reduce(devtrace.load(TRACE_DIR),
                                    family.is_kernel, family.kernel_cost)
        facts["trace"] = reduction
        chips = reduction.chips.values()
        device["busy_s"] = (sum(c.busy_ns for c in chips) * 1e-9
                            / max(len(chips), 1))
        device["window_s"] = reduction.window_ns * 1e-9
        result["metrics"] = read_metrics(cell["per_layer"], facts)
        result["breakdown"] = reduction.breakdown()
    else:
        result["metrics"] = read_metrics(cell["end_to_end"], facts)
    result["device"] = device
    result["setup_phases"] = phases
    result["checks"] = checks
    return result


def read_metrics(specs, facts: dict) -> dict:
    """Each metric that its reader finds something for, by name."""
    out = {}
    for spec in specs:
        got = registry.metric_reader(spec["name"])(facts)
        if got is None:
            continue
        got = got if isinstance(got, dict) else {"value": got}
        out[spec["name"]] = {"value": got.pop("value"), "unit": spec["unit"],
                             **got}
    return out


def tag(devices) -> str:
    d = describe(devices)
    return f"[{d['platform']} {d['kind']} x{d['count']}]"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload)
    t0 = time.perf_counter()
    imports_s = t0 - T_START
    devices = jax.devices()
    devices_s = time.perf_counter() - t0
    try:
        peaks = guard(devices, cell["chips"])
    except DeviceError as e:
        print(f"bench: {e} {tag(devices)}", file=sys.stderr, flush=True)
        return 1
    devices = devices[: cell["chips"]]
    cache = use_compile_cache()
    print(f"bench: {args.workload} seed {args.seed} compile cache {cache} "
          f"{tag(devices)}", file=sys.stderr, flush=True)

    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, peaks)
    finally:
        faulthandler.cancel_dump_traceback_later()
    phases = {"imports_s": imports_s, "devices_s": devices_s,
              **result["setup_phases"]}
    print("setup " + " ".join(f"{k} {v!r}" for k, v in phases.items())
          + f" {tag(devices)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{tag(devices)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
