"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Modules:
  micro_overhead     Fig 5  (no-dependency overhead, TTor vs STF)
  micro_deps         Fig 6  (dependency-management overhead)
  gemm_scaling       Fig 7  (distributed GEMM: scaling, block sweep, AMs)
  cholesky_scaling   Fig 9  (distributed Cholesky: scaling, block, rho)
  taskbench_scaling  Task Bench (1908.05790): dependence-pattern sweep over
                     discovery -> comm_plan -> executor, wire efficiency
  discovery_scaling  graph-build cost: lazy per-shard derivation (owned +
                     halo) vs the eager global scan, edge_frac guarded
  recovery           fault-recovery cost: Cholesky under seeded loss/dup/
                     rank-kill plans; recovery_seconds + rederived_frac
                     (guarded lower) from the RecoveryReport
  scheduler_stream   resident multi-tenant scheduler: per-task overhead of
                     the submission-stream path (sched_overhead_us) and
                     retirement health (live_frac), both guarded lower
  transport          per-comm-backend AM ping-pong latency (am_rtt_us,
                     guarded lower at the loose tol) and 1 MiB one-way
                     bandwidth, inproc threads vs multiproc OS processes

``--json [PATH]`` additionally writes a ``BENCH_<utc>.json`` artifact with
every row (plus each module's structured ``extra`` payload), so
us-per-task, wire-efficiency, and — since the segmented-scan executor —
``compile_seconds`` / ``hlo_bytes`` become a tracked trajectory across
PRs — see ROADMAP §Perf iteration log.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback


def compile_metrics(fn, *args):
    """Lower and compile a jittable callable, measuring the compile-cost
    columns the BENCH rows track: ``lower_seconds`` (trace + StableHLO
    emission), ``compile_seconds`` (XLA), and ``hlo_bytes`` (StableHLO
    module text size — the depth-proportional quantity the segmented-scan
    lowering exists to bound). Returns ``(compiled_callable, metrics)``.

    ``hlo_bytes`` is deterministic for a given jax version, so ratios of it
    between two lowerings of the same program (``hlo_frac`` in the deep
    Task-Bench rows) are guard-stable across machines.
    """
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    lower_s = time.perf_counter() - t0
    hlo_bytes = len(lowered.as_text())
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    return compiled, {
        "lower_seconds": round(lower_s, 4),
        "compile_seconds": round(compile_s, 4),
        "hlo_bytes": hlo_bytes,
    }

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; fix it up so the `benchmarks.*` imports resolve either way.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write rows to PATH (default BENCH_<utc>.json)")
    args = ap.parse_args()

    from benchmarks import (cholesky_scaling, discovery_scaling,
                            gemm_scaling, micro_deps, micro_overhead,
                            recovery, scheduler_stream,
                            taskbench_scaling, transport)

    modules = {
        "micro_overhead": micro_overhead,
        "micro_deps": micro_deps,
        "gemm_scaling": gemm_scaling,
        "cholesky_scaling": cholesky_scaling,
        "taskbench_scaling": taskbench_scaling,
        "discovery_scaling": discovery_scaling,
        "recovery": recovery,
        "scheduler_stream": scheduler_stream,
        "transport": transport,
    }
    if args.only:
        modules = {k: v for k, v in modules.items() if k in args.only}

    print("name,us_per_call,derived")
    failed = []
    rows = []

    def report(name: str, us: float, derived: str = "", extra=None) -> None:
        print(f"{name},{us:.3f},{derived}", flush=True)
        row = {"name": name, "us_per_call": us, "derived": derived}
        if extra:
            row.update(extra)
        rows.append(row)

    for name, mod in modules.items():
        try:
            mod.run(report)
        except Exception:
            failed.append(name)
            traceback.print_exc()

    if args.json is not None:
        path = args.json or time.strftime("BENCH_%Y%m%dT%H%M%SZ.json",
                                          time.gmtime())
        payload = {
            "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "python": platform.python_version(),
            "modules": sorted(modules),
            "failed": failed,
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {path} ({len(rows)} rows)", file=sys.stderr)

    if failed:
        sys.exit(f"benchmark module(s) failed: {failed}")


if __name__ == "__main__":
    main()
