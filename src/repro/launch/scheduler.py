"""Scheduler-service launcher: a resident multi-tenant submission demo.

    python -m repro.launch.scheduler --shards 2 --clients 4 \
        --submissions 8 --verify

Starts one :class:`repro.sched.SchedulerService` (ranks stay resident
between submissions), registers N clients with distinct fair-share
weights, and streams M submissions per client into it concurrently —
cycling through the four Task-Bench dependence patterns plus a blocked
Cholesky as the linalg family. ``--verify`` replays every distinct graph
through the one-shot ``Graph.run_host`` path and checks the stream's
results are bit-identical; the exit prints per-client accounting
(tasks / bytes / wall) and the service's retirement stats (``live_frac``
near 0 means memory tracked the live frontier, not the stream's history).

Chaos mode exercises the survivable-stream machinery:

    python -m repro.launch.scheduler --kill 1:40 --chaos 0.1 --verify

``--kill RANK:AT_MSG`` crashes a resident rank at its AT_MSG-th user AM
send; ``--chaos P`` adds P message loss and duplication on every edge;
``--deadline S`` bounds each submission's life. The exit then prints the
:class:`~repro.core.faults.RecoveryReport` — replayed bus commands and
sends, re-executed tasks, forwarded AMs — plus ``sched_recover_ms``
(death declaration -> the at-death in-flight set drained).
"""

import argparse
import sys
import threading
import time
from pathlib import Path


def run_stream(svc, n_clients: int, n_submissions: int, *, width: int,
               depth: int, nb: int, seed: int = 7,
               deadline: float = None):
    """Drive ``n_clients`` concurrent client threads, each submitting
    ``n_submissions`` mixed PTGs (Task-Bench patterns + Cholesky, each in
    a fresh namespace). Returns ``{client: [(kind, result_blocks)]}``;
    a submission shed by its ``deadline`` yields ``(kind, None)``."""
    from benchmarks.taskbench_scaling import (taskbench_blocks,
                                              taskbench_bodies,
                                              taskbench_graph)
    from repro.linalg.cholesky import (cholesky_bodies,
                                       cholesky_bodies_numpy,
                                       cholesky_graph, make_spd_blocks)

    patterns = ("stencil", "fft", "tree", "random")
    n = svc.n_shards
    tb_blocks = taskbench_blocks(width, depth, seed=seed)
    tb_bodies = taskbench_bodies()
    ch_blocks, _ = make_spd_blocks(nb, 4, seed=seed)
    # forked rank processes must not call into the parent's XLA runtime
    ch_bodies = cholesky_bodies_numpy() \
        if getattr(svc, "transport", None) == "multiproc" \
        else cholesky_bodies()
    results: dict = {}

    def client_thread(name: str, weight: float) -> None:
        from repro.sched import DeadlineExceeded

        c = svc.client(name, weight=weight)
        futs = []
        for j in range(n_submissions):
            ns = f"{name}/{j}"
            if j % len(patterns) == len(patterns) - 1 and j:
                futs.append(("cholesky", c.submit(
                    cholesky_graph(nb, n, 1, 4), ch_blocks, ch_bodies,
                    namespace=ns, deadline=deadline)))
            else:
                p = patterns[j % len(patterns)]
                g, _ = taskbench_graph(p, width, depth, n, seed=seed)
                futs.append((p, c.submit(g, tb_blocks, tb_bodies,
                                         namespace=ns, deadline=deadline)))
        out = []
        for kind, f in futs:
            try:
                out.append((kind, f.result(svc.timeout)))
            except DeadlineExceeded:
                out.append((kind, None))   # cleanly shed, never a hang
        results[name] = out

    threads = [threading.Thread(target=client_thread,
                                args=(f"client{i}", float(i + 1)),
                                daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--submissions", type=int, default=8,
                    help="PTGs per client")
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--nb", type=int, default=4,
                    help="Cholesky blocks per dimension")
    ap.add_argument("--threads", type=int, default=2,
                    help="worker threads per rank")
    ap.add_argument("--verify", action="store_true",
                    help="check bit-identity against one-shot executions")
    ap.add_argument("--kill", default=None, metavar="RANK:AT_MSG",
                    help="crash a resident rank at its AT_MSG-th AM send")
    ap.add_argument("--chaos", type=float, default=0.0, metavar="P",
                    help="message loss AND duplication probability")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-submission deadline in seconds")
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-injection RNG seed")
    ap.add_argument("--transport", default=None,
                    choices=("inproc", "multiproc"),
                    help="comm backend the resident ranks run on "
                         "(multiproc = one OS process per rank)")
    args = ap.parse_args()

    # benchmarks/ lives at the repo root, beside src/
    sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.sched import SchedulerService

    use_compile_cache()

    plan = None
    if args.kill or args.chaos:
        from repro.core.faults import FaultPlan

        kill = {}
        if args.kill:
            rank, at = args.kill.split(":")
            kill[int(rank)] = int(at)
        plan = FaultPlan(seed=args.seed, drop=args.chaos,
                         duplicate=args.chaos, kill=kill)

    t0 = time.monotonic()
    with SchedulerService(args.shards, n_threads=args.threads,
                          timeout=300.0, faults=plan,
                          transport=args.transport) as svc:
        results = run_stream(svc, args.clients, args.submissions,
                             width=args.width, depth=args.depth, nb=args.nb,
                             deadline=args.deadline)
    wall = time.monotonic() - t0
    stats = svc.stats()

    total_subs = sum(len(v) for v in results.values())
    print(f"{args.clients} clients x {args.submissions} submissions on "
          f"{args.shards} resident shards: {total_subs} PTGs in {wall:.2f}s")
    for name in sorted(results):
        cs = stats["clients"][name]
        print(f"  {name}: {cs['completed']} completed, {cs['tasks']} tasks, "
              f"{cs['bytes']} bytes, {cs['wall_seconds']:.2f}s wall")
    print(f"retirement: blocks_hwm={stats['blocks_hwm']} / "
          f"blocks_total={stats['blocks_total']} "
          f"(live_frac={stats['live_frac']:.3f})")
    shed = sum(1 for rows in results.values() for _, out in rows
               if out is None)
    if shed:
        print(f"shed: {shed} submissions hit their deadline (clean "
              "DeadlineExceeded, no hangs)")
    if plan is not None and svc.recovery_report is not None:
        r = svc.recovery_report.to_dict()
        cap = svc.capacity()
        print(f"recovery: deaths={r['deaths']} "
              f"bus_replayed={r['bus_replayed']} "
              f"replayed_sends={r['replayed_sends']} "
              f"reexecuted_tasks={r['reexecuted_tasks']} "
              f"forwarded_ams={r['forwarded_ams']} "
              f"retries={r['retries']} dup_suppressed={r['dup_suppressed']}")
        if cap["sched_recover_ms"] is not None:
            print(f"recovery: sched_recover_ms="
                  f"{cap['sched_recover_ms']:.1f} "
                  f"(live_ranks={cap['live_ranks']}/{cap['n_shards']})")

    if args.verify:
        from benchmarks.taskbench_scaling import (taskbench_blocks,
                                                  taskbench_bodies,
                                                  taskbench_graph)
        from repro.linalg.cholesky import (cholesky_bodies,
                                           cholesky_bodies_numpy,
                                           cholesky_graph, make_spd_blocks)

        tb_blocks = taskbench_blocks(args.width, args.depth, seed=7)
        ch_blocks, _ = make_spd_blocks(args.nb, 4, seed=7)
        ch_bodies = cholesky_bodies_numpy() \
            if args.transport == "multiproc" else cholesky_bodies()
        refs = {}
        for kind in {k for rows in results.values() for k, _ in rows}:
            if kind == "cholesky":
                refs[kind] = cholesky_graph(args.nb, args.shards, 1, 4) \
                    .run_host(ch_blocks, ch_bodies,
                              n_threads=args.threads)
            else:
                g, _ = taskbench_graph(kind, args.width, args.depth,
                                       args.shards, seed=7)
                refs[kind] = g.run_host(tb_blocks, taskbench_bodies(),
                                        n_threads=args.threads)
        for name, rows in results.items():
            for kind, out in rows:
                if out is None:
                    continue   # shed by deadline: nothing to compare
                for blk, v in out.items():
                    assert np.array_equal(np.asarray(v),
                                          np.asarray(refs[kind][blk])), \
                        (name, kind, blk)
        print(f"verify: all {total_subs} submissions bit-identical to "
              f"one-shot executions")


if __name__ == "__main__":
    main()
