"""Property tests for the segmented-scan lowering's host-side machinery:
comm signatures, run segmentation, segment-padded wire accounting, table
memoization, and the ``plan_lowering`` policy (unrolled / segmented scan /
dense scan, with the loud fragmented fallback).

Hypothesis (real in CI, deterministic stub locally) hammers random layered
block-PTGs — bit-identity of the segmented executors vs the unrolled and
dense-scan references runs on 8 emulated devices in
``tests/multi_device_cases.py`` (cases ``lowering_identity`` and
``segmented_identity``).

Also the trailing updates that read their operands from the slot array by
index (``from_slots``) in every lowering: what they count, what they compute
on one shard, and the gathers the other bodies keep (four shards:
``tests/multi_device_cases.py``, case ``indexed_bodies``).
"""

import logging

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.core.discovery import segment_runs
from repro.core.schedule import build_block_program
from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.cholesky import (assemble_lower, cholesky_bodies,
                                   cholesky_graph, make_spd_blocks)
from repro.linalg.gemm import gemm_2d_graph, gemm_bodies

from tests.test_schedule_property import random_layered_ptg


# --------------------------------------------------------- segment_runs

@settings(deadline=None, max_examples=25)
@given(items=st.lists(st.integers(0, 3), min_size=0, max_size=30))
def test_segment_runs_partitions_into_maximal_runs(items):
    runs = segment_runs(items)
    # exact partition of [0, len), in order
    assert [i for s, e in runs for i in range(s, e)] == list(range(len(items)))
    for s, e in runs:
        assert e > s
        assert all(items[i] == items[s] for i in range(s, e))  # constant
    for (s1, e1), (s2, e2) in zip(runs, runs[1:]):             # maximal
        assert e1 == s2
        assert items[s1] != items[s2]


# ------------------------------------------- signatures and segmentation

@settings(deadline=None, max_examples=15,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    n_layers=st.integers(2, 6),
    width=st.integers(1, 6),
    n_shards=st.integers(1, 5),
    fan_in=st.integers(1, 4),
    comm=st.sampled_from(["dense", "sparse", "auto"]),
    seed=st.integers(0, 2**31),
)
def test_segments_partition_by_signature(n_layers, width, n_shards,
                                         fan_in, comm, seed):
    rng = np.random.default_rng(seed)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(
        rng, n_layers, width, n_shards, fan_in)
    prog = build_block_program(spec)
    W = prog.schedule.n_wavefronts
    sigs = [prog.comm_signature(w, comm) for w in range(W)]
    segs = prog.segments(comm)

    # exact partition, constant within, different across boundaries
    assert [w for s, e in segs for w in range(s, e)] == list(range(W))
    for s, e in segs:
        assert all(sigs[w] == sigs[s] for w in range(s, e))
    for (s1, _e1), (s2, _e2) in zip(segs, segs[1:]):
        assert sigs[s1] != sigs[s2]

    for w, sig in enumerate(sigs):
        choice = prog.lowered_pattern(w, comm)
        assert sig[0] == choice  # signature kind == lowering choice
        if sig[0] == "ppermute":
            # the static scan-body structure: the wavefront's own rounds
            assert sig[1] == tuple(tuple(r.perm)
                                   for r in prog.sparse_exchange[w])
        if comm == "dense":
            assert sig[0] in ("all_to_all", "none")


@settings(deadline=None, max_examples=15,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    n_layers=st.integers(2, 6),
    width=st.integers(1, 6),
    n_shards=st.integers(2, 5),
    fan_in=st.integers(1, 4),
    seed=st.integers(0, 2**31),
)
def test_segmented_comm_stats_accounting(n_layers, width, n_shards,
                                         fan_in, seed):
    rng = np.random.default_rng(seed)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(
        rng, n_layers, width, n_shards, fan_in)
    prog = build_block_program(spec)

    auto = prog.comm_stats(comm="auto")
    seg = prog.comm_stats(comm="auto", segmented=True)
    # same payload, only padding differs; per-segment padding can never
    # undercut the per-wavefront exact padding of the unrolled lowering
    assert seg["real_bytes"] == auto["real_bytes"]
    assert seg["n_segments"] == len(prog.segments("auto"))
    assert seg["total_wire_bytes"] >= auto["total_wire_bytes"]
    if seg["total_wire_bytes"]:
        assert 0.0 < seg["wire_efficiency"] <= 1.0
    for row_seg, row_auto in zip(seg["per_wavefront"],
                                 auto["per_wavefront"]):
        assert row_seg["pattern"] == row_auto["pattern"]
        assert row_seg["wire_blocks"] >= row_auto["wire_blocks"]
        assert row_seg["real_blocks"] == row_auto["real_blocks"]
    # the per-segment breakdown re-sums to the totals
    assert sum(r["real_bytes"] for r in seg["segments"]) == seg["real_bytes"]
    assert (sum(r["padded_bytes"] for r in seg["segments"])
            == seg["padded_bytes"])
    for r in seg["segments"]:
        assert 0 <= r["start"] < r["stop"]
        assert r["wavefronts"] == r["stop"] - r["start"]
        assert r["padded_bytes"] >= 0


# ----------------------------------------------------------- memoization

def test_lowered_tables_are_memoized():
    """The O(W·n·T) numpy stacking runs once per (schedule, mode): repeat
    calls return the *same objects* from the program's cache."""
    rng = np.random.default_rng(7)
    spec, _bodies, _blocks, _oracle = random_layered_ptg(rng, 5, 4, 3, 2)
    prog = build_block_program(spec)

    assert prog._dense_scan_tables() is prog._dense_scan_tables()
    assert (prog._segment_tables("auto", 0.5, False)
            is prog._segment_tables("auto", 0.5, False))
    assert (prog._segment_tables("auto", 0.5, True)
            is prog._segment_tables("auto", 0.5, True))
    # distinct modes get distinct cache entries
    assert (prog._segment_tables("auto", 0.5, False)
            is not prog._segment_tables("auto", 0.5, True))
    for w in range(prog.schedule.n_wavefronts):
        assert prog._split_tables(w) == prog._split_tables(w)
        assert prog._split_tables(w)[0] is prog._split_tables(w)[0]


# -------------------------------------------------- plan_lowering policy

def _taskbench(pattern, width, depth, n_shards):
    from benchmarks.taskbench_scaling import taskbench_spec

    spec, _deps = taskbench_spec(pattern, width, depth, n_shards, 4)
    return build_block_program(spec)


def test_plan_shallow_unrolls():
    prog = _taskbench("stencil", 8, 6, 4)
    plan = prog.plan_lowering(unroll_cap=64)
    assert plan["mode"] == "unrolled" and not plan["discards"]


def test_plan_deep_sparse_segments():
    """Past the unroll cap, a stencil schedule keeps its sparse wire via
    the segmented scan — the old dense-scan cliff is gone."""
    prog = _taskbench("stencil", 16, 70, 8)
    plan = prog.plan_lowering(unroll_cap=64)
    assert plan["mode"] == "segmented_scan"
    assert plan["n_segments"] <= 4
    assert not plan["discards"]
    # and the segmented wire matches the unrolled auto reference
    seg = prog.comm_stats(comm="auto", segmented=True)
    auto = prog.comm_stats(comm="auto")
    assert seg["wire_efficiency"] >= 0.9 * auto["wire_efficiency"]


def test_plan_deep_fragmented_takes_union_cover():
    """fft's stride cycling gives every wavefront a different ppermute
    signature: too fragmented to segment *exactly* — but the union
    permutation cover folds the whole sparse run into a handful of scans,
    so the policy keeps the sparse wire instead of warning-and-falling-back
    to the dense scan. Also exercises ragged shapes: the exact fft run list
    contains single-wavefront segments."""
    prog = _taskbench("fft", 16, 70, 8)
    plan = prog.plan_lowering(unroll_cap=64)
    assert plan["mode"] == "union_cover"
    assert plan["cover"] == "union"
    assert not plan["discards"]
    assert plan["n_segments"] > 64            # exact cover fragments...
    assert plan["n_segments_union"] <= 4      # ...the union cover does not
    assert (plan["wire_efficiency_union"]
            > plan["wire_efficiency_dense_scan"])
    assert any(e - s == 1 for s, e in prog.segments("auto"))

    # every wavefront's pairs are spanned by its union segment's rounds
    # (realization would raise otherwise), and the padding is accounted:
    # union wire >= exact wire, same payload
    union = prog.comm_stats(comm="auto", segmented=True, cover="union")
    exact = prog.comm_stats(comm="auto")
    assert union["real_bytes"] == exact["real_bytes"]
    assert union["total_wire_bytes"] >= exact["total_wire_bytes"]
    assert union["n_segments"] == plan["n_segments_union"]


def test_plan_hopeless_fragmentation_falls_back_loudly(caplog):
    """When even the union cover cannot fit the segment cap, the policy
    still falls back to the dense scan — explicitly (discards=True + a
    logged warning), never silently."""
    prog = _taskbench("fft", 16, 70, 8)
    plan = prog.plan_lowering(unroll_cap=64, segment_cap=0)
    assert plan["mode"] == "dense_scan"
    assert plan["discards"]
    assert "fragmented" in plan["reason"]

    # auto_executor logs the discard before touching the mesh; a 1-device
    # mesh then fails the shard-count check, which is fine — the warning
    # must already be out.
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    with caplog.at_level(logging.WARNING, logger="repro.core.schedule"):
        with pytest.raises(ValueError, match="shards"):
            prog.auto_executor({}, mesh, unroll_cap=64, segment_cap=0)
    assert any("DISCARDING" in r.message for r in caplog.records)


def test_plan_dense_request_and_genuinely_dense():
    # explicit dense ask -> pure dense scan, no discard
    prog = _taskbench("stencil", 16, 70, 8)
    plan = prog.plan_lowering(unroll_cap=64, comm="dense", overlap=False)
    assert plan["mode"] == "dense_scan" and not plan["discards"]
    # random at 4 shards classifies dense everywhere: with no overlap asked
    # there is no sparsity to keep -> pure dense scan, not a discard
    prog = _taskbench("random", 16, 70, 4)
    plan = prog.plan_lowering(unroll_cap=64, overlap=False)
    assert plan["mode"] == "dense_scan" and not plan["discards"]
    assert "genuinely dense" in plan["reason"]
    # but with overlap (the default) the segmented scan carries it
    plan = prog.plan_lowering(unroll_cap=64)
    assert plan["mode"] == "segmented_scan"


def test_executor_rejects_unknown_comm():
    prog = _taskbench("stencil", 4, 3, 1)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    with pytest.raises(ValueError, match="unknown comm policy"):
        prog.executor({}, mesh, comm="bogus")


# ------------------------------------ trailing updates read from the slots

# every lowering ``BlockProgram.executor`` builds, by its arguments
LOWERINGS = {
    "unrolled": dict(scan=False),
    "unrolled_overlap": dict(scan=False, overlap=True),
    "dense_scan": dict(scan=True),
    "segmented_scan": dict(scan=True, comm="auto"),
    "segmented_scan_overlap": dict(scan=True, comm="auto", overlap=True),
    "union_cover": dict(scan=True, comm="auto", cover="union"),
    "union_cover_overlap": dict(scan=True, comm="auto", overlap=True,
                                cover="union"),
}


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1shard", "2x2"])
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_indexed_rows_are_the_trailing_updates(lowering, grid):
    """With ``task_matmul`` the syrk and gemm bodies read their operands by
    index: ``ttor.indexed_rows`` is their rows of the lowering, trash rows
    included. The jnp bodies and GEMM's ``c + a b`` have no indexed form
    and count none. (Lowering builds the executor; nothing is traced.)"""
    trace.reset()
    kw = LOWERINGS[lowering]
    mesh = jax.sharding.AbstractMesh((grid[0] * grid[1],), ("shards",))
    prog = cholesky_graph(6, *grid, 8).to_program()
    for matmul, indexed in ((task_matmul, ("syrk", "gemm")), (None, ())):
        prog.executor(cholesky_bodies(matmul), mesh, **kw)
        got = trace.snapshot()["gauges"]
        rows = got["ttor.task_rows_by_type"]
        assert got["ttor.indexed_rows"] == sum(rows[t] for t in indexed)
        assert rows["gemm"] > 0 and rows["syrk"] > 0
    gemm = gemm_2d_graph(4, *grid, 8, staged=True).to_program()
    gemm.executor(gemm_bodies(task_matmul), mesh, **kw)
    got = trace.snapshot()["gauges"]
    assert got["ttor.indexed_rows"] == 0 < got["ttor.task_rows"]
    trace.reset()


@pytest.mark.parametrize("lowering", ["unrolled", "dense_scan",
                                      "segmented_scan",
                                      "segmented_scan_overlap"])
def test_indexed_updates_match_the_jnp_bodies(lowering):
    """One shard, the Pallas kernel interpreted: the factor from syrk and
    gemm read by index equals the jnp bodies' within float32 rounding.
    (The union cover is the segmented scan on one shard; four shards run
    every lowering in ``multi_device_cases indexed_bodies``.)"""
    nb, b = 5, 16
    prog = cholesky_graph(nb, 1, 1, b).to_program()
    blocks, a = make_spd_blocks(nb, b)
    packed = jnp.asarray(prog.pack(blocks))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))

    def factor(matmul):
        run = jax.jit(prog.executor(cholesky_bodies(matmul), mesh,
                                    **LOWERINGS[lowering]))
        return assemble_lower(prog.unpack(run(packed)), nb, b)

    got = factor(task_matmul)
    np.testing.assert_allclose(got, factor(None), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, np.linalg.cholesky(a),
                               rtol=5e-3, atol=5e-3)


def _gathers(prog, bodies) -> int:
    """Gather operations in the unrolled one-shard executor's StableHLO."""
    b0, b1 = prog.spec.block_shape
    x = jax.ShapeDtypeStruct((1, prog.n_slots, b0, b1), jnp.float32)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    run = jax.jit(prog.executor(bodies, mesh, scan=False))
    return run.lower(x).as_text().count('"stablehlo.gather"(')


def test_bodies_without_an_indexed_form_keep_their_gather():
    """Unrolled, each (wavefront, type) computed gathers its operands once,
    except where the body reads them by index: the jnp Cholesky bodies and
    GEMM's keep every gather, ``task_matmul``'s syrk and gemm drop theirs."""
    prog = cholesky_graph(5, 1, 1, 16).to_program()
    calls = {t: sum(t in tbl for tbl in prog.tables) for t in prog.types}
    assert _gathers(prog, cholesky_bodies()) == sum(calls.values())
    assert (_gathers(prog, cholesky_bodies(task_matmul))
            == calls["potrf"] + calls["trsm"])
    gemm = gemm_2d_graph(2, 1, 1, 16, staged=True).to_program()
    n_calls = sum(len(tbl) for tbl in gemm.tables)
    assert (_gathers(gemm, gemm_bodies(task_matmul))
            == _gathers(gemm, gemm_bodies()) == n_calls)
