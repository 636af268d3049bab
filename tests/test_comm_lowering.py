"""Property tests for the classified exchange lowering.

Hypothesis (real in CI, deterministic stub locally) hammers random layered
block-PTGs through discovery + ``build_block_program`` and checks, against
a brute-force walk of the PTG's cross-shard edges:

- ``comm_stats`` byte accounting: real bytes == distinct (block, dst shard)
  cross edges per producer wavefront, under every lowering policy;
- pattern classification: per-pair counts, density, and the ppermute round
  decomposition (partial permutations covering each pair exactly once);
- the halo split: independent + dependent partitions each wavefront, and
  dependent tasks are exactly the message targets of the previous one.

(Bit-identity of the sparse/overlap executors vs the unrolled dense
reference runs on 8 emulated devices in ``tests/multi_device_cases.py`` —
cases ``lowering_identity`` and ``taskbench_identity``; the trailing updates
read by index on four shards, in every lowering, run there as case
``indexed_bodies``.)
"""

import os
import subprocess
import sys
from collections import defaultdict

import numpy as np
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.schedule import build_block_program

from tests.test_schedule_property import random_layered_ptg


def brute_force_cross_edges(spec, level_of):
    """{producer wavefront: {(src, dst): set(blocks)}} walked directly off
    the PTG — one copy per (block, dst shard), the large-AM contract."""
    n = spec.n_shards
    edges = defaultdict(lambda: defaultdict(set))
    tasks = list(level_of)
    for k in tasks:
        dst = spec.ptg.mapping(k) % n
        ops = set(spec.operands(k))
        for d in spec.ptg.in_deps(k):
            src = spec.ptg.mapping(d) % n
            blk = spec.block_of(d)
            if src != dst and blk in ops:
                edges[level_of[d]][(src, dst)].add(blk)
    return edges


@settings(deadline=None, max_examples=15,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    n_layers=st.integers(2, 5),
    width=st.integers(1, 6),
    n_shards=st.integers(1, 5),
    fan_in=st.integers(1, 4),
    seed=st.integers(0, 2**31),
)
def test_comm_accounting_matches_brute_force(n_layers, width, n_shards,
                                             fan_in, seed):
    rng = np.random.default_rng(seed)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(
        rng, n_layers, width, n_shards, fan_in)
    prog = build_block_program(spec)
    edges = brute_force_cross_edges(spec, prog.schedule.level_of)

    block_bytes = prog.comm_stats()["block_bytes"]
    want_real = {w: sum(len(b) for b in pairs.values())
                 for w, pairs in edges.items()}

    for comm in ("dense", "sparse", "auto"):
        st_ = prog.comm_stats(comm=comm)
        assert st_["real_bytes"] == sum(want_real.values()) * block_bytes
        assert st_["padded_bytes"] >= 0
        assert (st_["real_bytes"] + st_["padded_bytes"]
                == st_["total_wire_bytes"])
        if st_["total_wire_bytes"]:
            assert 0.0 < st_["wire_efficiency"] <= 1.0
        for w, row in enumerate(st_["per_wavefront"]):
            assert row["real_blocks"] == want_real.get(w, 0)
            assert row["wire_blocks"] >= row["real_blocks"]

    # sparse never ships more wire than dense (it may tie)
    sp = prog.comm_stats(comm="sparse")
    de = prog.comm_stats(comm="dense")
    au = prog.comm_stats(comm="auto")
    assert sp["total_wire_bytes"] <= de["total_wire_bytes"]
    assert au["total_wire_bytes"] <= de["total_wire_bytes"]


@settings(deadline=None, max_examples=15,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    n_layers=st.integers(2, 5),
    width=st.integers(1, 6),
    n_shards=st.integers(2, 5),
    fan_in=st.integers(1, 4),
    seed=st.integers(0, 2**31),
)
def test_pattern_classification_and_rounds(n_layers, width, n_shards,
                                           fan_in, seed):
    rng = np.random.default_rng(seed)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(
        rng, n_layers, width, n_shards, fan_in)
    prog = build_block_program(spec)
    edges = brute_force_cross_edges(spec, prog.schedule.level_of)

    for w, pat in enumerate(prog.patterns):
        want = {pair: len(blks)
                for pair, blks in edges.get(w, {}).items() if blks}
        assert pat.pair_counts == want
        assert pat.n_pairs == len(want)
        assert 0.0 <= pat.density <= 1.0
        assert pat.total == sum(want.values())

        # round decomposition: partial permutations, each pair exactly once
        seen = []
        for rnd in prog.sparse_exchange[w]:
            srcs = [p[0] for p in rnd.perm]
            dsts = [p[1] for p in rnd.perm]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)
            assert rnd.width == max(want[p] for p in rnd.perm)
            seen.extend(rnd.perm)
        assert sorted(seen) == sorted(want)

        # sparse wire slots account exactly: rounds x active pairs x width
        sp_row = prog.comm_stats(comm="sparse")["per_wavefront"][w]
        assert sp_row["wire_blocks"] == sum(
            r.wire_slots for r in prog.sparse_exchange[w])


@settings(deadline=None, max_examples=15,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    n_layers=st.integers(2, 5),
    width=st.integers(1, 6),
    n_shards=st.integers(1, 5),
    fan_in=st.integers(1, 4),
    seed=st.integers(0, 2**31),
)
def test_halo_split_partitions_wavefronts(n_layers, width, n_shards,
                                          fan_in, seed):
    rng = np.random.default_rng(seed)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(
        rng, n_layers, width, n_shards, fan_in)
    prog = build_block_program(spec)
    sched = prog.schedule

    for w in range(sched.n_wavefronts):
        arriving = {m.dst_task
                    for pairs in sched.messages.get(w - 1, {}).values()
                    for m in pairs if sched.level_of[m.dst_task] == w}
        for s, (indep, dep) in enumerate(sched.halo_split(w)):
            tasks = sched.shards[s].wavefronts[w]
            assert sorted(map(repr, indep + dep)) == sorted(map(repr, tasks))
            assert all(k in arriving for k in dep)
            assert all(k not in arriving for k in indep)


def test_indexed_bodies_on_four_shards():
    """The exchange lowerings with trailing updates that read the slot
    array by index: every lowering on four emulated devices, in a child
    process (``tests.multi_device_cases indexed_bodies``)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "tests.multi_device_cases", "indexed_bodies"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"\nstdout:{proc.stdout}\nstderr:{proc.stderr}"
    assert "CASE indexed_bodies OK" in proc.stdout, proc.stdout
