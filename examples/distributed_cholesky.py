"""The paper's flagship app end-to-end: distributed blocked Cholesky
declared ONCE via the unified ``repro.ptg`` front-end and executed on BOTH
backends from that single definition —

  (a) the host TaskTorrent runtime: async tasks + work stealing + one-sided
      active messages + distributed completion detection;
  (b) the compiled SPMD executor: parallel DAG discovery -> wavefront
      schedule -> shard_map with classified sparse/dense exchanges.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 to see real
multi-device sharding in (b).

  PYTHONPATH=src python examples/distributed_cholesky.py --nb 8 --block 32
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.linalg.cholesky import (assemble_lower, cholesky_executor,
                                   cholesky_graph, make_spd_blocks)


def np_bodies():
    return {
        "potrf": lambda a: np.linalg.cholesky(a),
        "trsm": lambda a, l_kk: np.linalg.solve(l_kk, a.T).T,
        "syrk": lambda a, l: a - l @ l.T,
        "gemm": lambda a, li, lj: a - li @ lj.T,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nb", type=int, default=8)
    ap.add_argument("--block", type=int, default=32)
    ap.add_argument("--grid", type=int, nargs=2, default=(2, 2))
    args = ap.parse_args()
    pr, pc = args.grid
    nb, b = args.nb, args.block
    n = nb * b

    graph = cholesky_graph(nb, pr, pc, b)   # ONE declarative definition
    blocks, a = make_spd_blocks(nb, b)
    want = np.linalg.cholesky(a)

    # (a) host runtime, wired from the derived out-edges
    t0 = time.perf_counter()
    host = graph.run_host(blocks, np_bodies(), n_threads=2)
    t_host = time.perf_counter() - t0
    l_host = assemble_lower(host, nb, b)
    print(f"[host runtime]  N={n} on {pr}x{pc} ranks: {t_host * 1e3:7.1f} ms  "
          f"max|err|={np.abs(l_host - want).max():.2e}")

    # (b) compiled backend: classified sparse exchange + comm/compute overlap
    prog = graph.to_program()
    n_dev = len(jax.devices())
    if n_dev < pr * pc:
        print(f"[compiled]      skipped: {pr * pc} shards need {pr * pc} "
              f"devices, have {n_dev} (on the CPU set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={pr * pc})")
    else:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[: pr * pc]),
                                 ("shards",))
        with mesh:
            run = jax.jit(cholesky_executor(prog, mesh))
            out = prog.unpack(run(jnp.asarray(prog.pack(blocks))))  # warmup
            t0 = time.perf_counter()
            out = prog.unpack(
                jax.block_until_ready(run(jnp.asarray(prog.pack(blocks)))))
            t_comp = time.perf_counter() - t0
        l_comp = assemble_lower(out, nb, b)
        print(f"[compiled SPMD] N={n} on {pr * pc} shards: "
              f"{t_comp * 1e3:7.1f} ms  "
              f"max|err|={np.abs(l_comp - want).max():.2e}")
    st = prog.comm_stats(comm="auto")
    dense = prog.comm_stats(comm="dense")
    print(f"schedule: {prog.schedule.n_wavefronts} wavefronts | wire "
          f"{st['real_bytes'] / 1e6:.2f} MB real / "
          f"{st['padded_bytes'] / 1e6:.2f} MB padded "
          f"(efficiency {st['wire_efficiency']:.2f} vs "
          f"{dense['wire_efficiency']:.2f} dense all_to_all)")


if __name__ == "__main__":
    main()
