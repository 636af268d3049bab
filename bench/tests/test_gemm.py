"""The GEMM family on the CPU at a small size.

A sound run of the harness on a small GEMM cell is correct, and the control
and each fault a GEMM cell can have are not; the plain reference agrees
with the program's executor in each lowering; ``ttor.gather_bytes`` counts
the blocks the operand gathers copy; and ``exec_gather_roofline`` reads the
counter against the gather's device time (its scope and what is left
unscoped), or nothing.
"""

from __future__ import annotations

import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, Mesh

from bench import registry, run, scopes
from bench.devtrace import HloOp
from bench.families import gemm as fam
from bench.references import gemm as reference
from repro.core import trace
from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.cholesky import (cholesky_bodies, cholesky_executor,
                                   cholesky_graph)
from repro.linalg.gemm import gemm_2d_graph, gemm_bodies, gemm_executor

B = 128
# The limit at these sizes, set from CPU readings as a cell's limit is set
# from chip readings: the program reads 0.0 at N = 384 and 512 on three
# seeds each (on the CPU the reference's k-ordered block sums round exactly
# as the program's do), its control (the reference in bf16_3x) 4.0e-6 …
# 4.9e-6; 10x below the control's least.
LIMIT = 4e-7
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 7


def small_cell(nb: int, directory: Path) -> dict:
    """The ``gemm_n16k`` cell at nb x nb blocks of B, on one device."""
    spec = registry.benchmark()
    cell = next(w for w in spec["workloads"] if w["name"] == "gemm_n16k")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = {**json.loads((registry.ROOT / conf["file"]).read_text()),
              "nb": nb, "b": B,
              "limits": {"c_err": LIMIT, "solves_differing": 0}}
    path = directory / "small.json"
    path.write_text(json.dumps(config))
    spec["configs"] = [{**conf, "file": str(path)}]
    spec["workloads"] = [cell]
    return registry.cell(cell["name"], spec)


@contextlib.contextmanager
def fault(name: str, nb: int):
    """Break the timed path underneath the harness."""
    real = fam.gemm_executor
    if name == "sound":
        yield
    elif name == "state_unchanged":       # the executor hands its input back
        with mock.patch.object(fam, "gemm_executor",
                               lambda prog, mesh, **kw: lambda x: x):
            yield
    elif name == "products_zero":         # every block product is zero
        with mock.patch.object(
                fam, "task_matmul",
                lambda a, b: jnp.zeros((a.shape[0], b.shape[1]), a.dtype)):
            yield
    elif name == "answer_altered":        # one C entry off by 1e-4
        def altered(prog, mesh, **kw):
            ex = real(prog, mesh, **kw)
            s, slot = prog.slot_of[("C", nb - 1, 0)]
            return lambda x: ex(x).at[s, slot, 0, 0].add(1e-4)

        with mock.patch.object(fam, "gemm_executor", altered):
            yield
    elif name == "k_step_dropped":        # one C block misses its last k
        def dropped(prog, mesh, **kw):
            ops, out = prog.tables[-1]["gemm"]
            out = out.copy()
            out[0, 0] = prog.trash
            prog.tables[-1] = {**prog.tables[-1], "gemm": (ops, out)}
            return real(prog, mesh, **kw)

        with mock.patch.object(fam, "gemm_executor", dropped):
            yield
    else:
        raise ValueError(f"no fault {name!r}")


def run_small(name: str, nb: int, seed: int = SEED,
              seconds: float = 0.2) -> dict:
    with tempfile.TemporaryDirectory() as d:
        cell = small_cell(nb, Path(d))
        with fault(name, nb):
            return run.run(cell, seed, seconds, False, jax.devices()[:1],
                           PEAKS, time.perf_counter())


# ------------------------------------------------------------- correct

@pytest.mark.parametrize("nb", [3, 4])
def test_a_sound_gemm_run_is_correct(nb):
    res = run_small("sound", nb)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert res["checks"]["c_err"]["value"] < LIMIT / 2
    assert res["checks"]["solves_differing"]["value"] == 0
    assert set(res["metrics"]) == {"solve_s", "setup_s"}


@pytest.mark.parametrize("fault_name", ["state_unchanged", "products_zero",
                                        "answer_altered", "k_step_dropped"])
def test_a_gemm_fault_under_the_timed_path_is_not_correct(fault_name):
    res = run_small(fault_name, 4)
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
    assert res["checks"]["c_err"]["value"] > LIMIT


@pytest.mark.parametrize("nb", [3, 4])
def test_the_gemm_control_is_not_correct(nb):
    """The reference one precision below the configuration's (bf16_3x for
    highest), in the program's place, reads above the limit, and the checks
    that decide a run's ``correct`` refuse it."""
    with tempfile.TemporaryDirectory() as d:
        config = small_cell(nb, Path(d))["config_data"]
    numbers = fam.control(config, jax.devices()[:1], 11)
    assert numbers["c_err"] > 3 * LIMIT
    checks = run.checks_of(numbers, config["limits"])
    assert not all(map(run.passes, checks.values()))


def test_the_control_dot_is_three_bf16_passes():
    """The control's dot is bf16_3x: each operand's high and low bf16 parts,
    every product but the low parts', summed in float32; not one bf16
    pass, and not float32."""
    import ml_dtypes

    kx, ky = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (64, 256), jnp.float32)
    y = jax.random.normal(ky, (256, 64), jnp.float32)

    def parts(v):
        v = np.asarray(v, np.float64)
        hi = v.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
        lo = (v - hi).astype(np.float32).astype(ml_dtypes.bfloat16)
        return hi, lo.astype(np.float64)

    (xh, xl), (yh, yl) = parts(x), parts(y)
    three = xh @ yh + xh @ yl + xl @ yh
    exact = np.asarray(x, np.float64) @ np.asarray(y, np.float64)
    got = np.asarray(jax.jit(reference.dot_high, static_argnums=0)(
        "ik,kj->ij", x, y), np.float64)
    scale = np.abs(exact).max()
    assert np.abs(got - three).max() / scale < 1e-6
    assert np.abs(got - exact).max() / scale > 1e-6
    assert np.abs(got - xh @ yh).max() / scale > 1e-4


def test_the_gemm_family_counts_the_product_and_its_kernel():
    """2 N^3 flops per solve whatever implements it; the vmapped kernel's
    calls cost 2 T b^3 flops, operands read once and C written once."""
    assert fam.solve_flops({"nb": 16, "b": 1024}) == 2 * 16384 ** 3
    shape, operands = (256, 1024, 1024), ((256, 1024, 1024),) * 2
    assert fam.kernel_cost(shape, operands) == (
        2.0 * 256 * 1024 ** 3, 4.0 * 3 * 256 * 1024 ** 2)
    assert fam.is_kernel(HloOp("vmap_jit_block_gemm__.32", "custom-call",
                               shape, operands))
    assert not fam.is_kernel(HloOp("fusion.7", "fusion", shape, operands))


# ----------------------------------------------------------- reference

@pytest.mark.parametrize("policy,mode", [
    ({}, "unrolled"), ({"unroll_cap": 0, "segment_cap": 1}, "segmented_scan"),
    ({"unroll_cap": 0}, "dense_scan")])
def test_the_reference_matches_the_executor(policy, mode):
    """``gemm_2d_graph(4, 1, 1, 128)`` through ``to_program`` and
    ``auto_executor`` against the plain reference on seeded random blocks,
    in the unrolled lowering and in the scans."""
    nb = 4
    prog = gemm_2d_graph(nb, 1, 1, B).to_program()
    assert prog.plan_lowering(**policy)["mode"] == mode
    rng = np.random.default_rng(3)
    a, b = (rng.standard_normal((nb, nb, B, B)).astype(np.float32)
            for _ in range(2))
    packed = prog.pack({**{("A", i, k): a[i, k] for i in range(nb)
                           for k in range(nb)},
                        **{("B", k, j): b[k, j] for k in range(nb)
                           for j in range(nb)}})
    mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
    with jax.default_matmul_precision("highest"):
        out = jax.jit(gemm_executor(prog, mesh, matmul=task_matmul,
                                    **policy))(packed)
    got = prog.unpack(out)
    c_ref = np.asarray(reference.gemm_blocks(jnp.asarray(a), jnp.asarray(b)))
    err = max(np.abs(got[("C", i, j)] - c_ref[i, j]).max()
              for i in range(nb) for j in range(nb))
    assert err / np.abs(c_ref).max() <= LIMIT
    exact = a.transpose(0, 2, 1, 3).reshape(nb * B, -1).astype(np.float64) @ \
        b.transpose(0, 2, 1, 3).reshape(nb * B, -1).astype(np.float64)
    exact = exact.reshape(nb, B, nb, B).transpose(0, 2, 1, 3)
    assert np.abs(c_ref - exact).max() / np.abs(exact).max() < 1e-6


# ------------------------------------------------------------- counter

LOWERINGS = {
    "unrolled": dict(scan=False),
    "segmented": dict(scan=True, comm="auto"),
    "union_cover": dict(scan=True, comm="auto", cover="union"),
    "dense_scan": dict(scan=True, comm="dense"),
}


@pytest.fixture
def record():
    trace.reset()
    yield
    trace.reset()


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
@pytest.mark.parametrize("graph,bodies,gathered", [
    (lambda: gemm_2d_graph(4, 1, 1, 8), lambda: gemm_bodies(task_matmul),
     {"sa", "sb", "gemm"}),
    (lambda: cholesky_graph(4, 1, 1, 8), lambda: cholesky_bodies(task_matmul),
     {"potrf", "trsm"}),
], ids=["gemm", "cholesky"])
def test_gather_bytes_counts_the_gathered_blocks(record, lowering, graph,
                                                 bodies, gathered):
    """Every lowering rung counts rows x arity x block bytes over the types
    the executor gathers for: all of GEMM's, Cholesky's potrf and trsm (its
    syrk and gemm read the slot array by index)."""
    prog = graph().to_program()
    prog.executor(bodies(), AbstractMesh((1,), ("shards",)),
                  **LOWERINGS[lowering])
    got = trace.snapshot()["gauges"]
    rows = got["ttor.task_rows_by_type"]
    assert {t for t in prog.types if rows[t]} >= gathered
    assert got["ttor.gather_bytes"] == sum(
        rows[t] * prog.arity[t] * 8 * 8 * 4 for t in gathered)
    if lowering == "unrolled":            # each wavefront's own rows
        assert rows == {t: sum(tbl[t][0].shape[1] for tbl in prog.tables
                               if t in tbl) for t in prog.types}


@pytest.mark.parametrize("cell,gather_bytes", [
    ("gemm_n16k", 12_800 * 1024 ** 2 * 4),
    ("chol_n28k", 18_689_818_624),
    ("chol_n32k_2x2", 4 * 24_578_621_440),
])
def test_the_cells_gather_bytes(record, cell, gather_bytes):
    """The cells' programs, lowered as the benchmark lowers them (no
    compile): GEMM gathers 512 send blocks and 3 x 4096 gemm operands of
    4 MiB; Cholesky its potrf and trsm rows, summed over the chips."""
    got = registry.cell(cell)
    cfg = got["config_data"]
    graph, executor = {"gemm": (gemm_2d_graph, gemm_executor),
                       "cholesky": (cholesky_graph, cholesky_executor)}[
        cfg["family"]]
    prog = graph(cfg["nb"], *cfg["grid"], cfg["b"]).to_program()
    executor(prog, AbstractMesh((got["chips"],), ("shards",)),
             matmul=task_matmul)
    assert trace.snapshot()["gauges"]["ttor.gather_bytes"] == gather_bytes


# -------------------------------------------------------------- reader

def read(facts):
    return registry.metric_reader("exec_gather_roofline")(facts)


def test_the_reader_is_the_counted_least_time_over_the_gather_time(
        record, monkeypatch):
    trace.gauge("ttor.gather_bytes", 4 * 81.9e9)
    monkeypatch.setattr(scopes, "of", lambda facts: mock.Mock(
        per_solve_ms=lambda bucket: {"gather": 300.0,
                                     "unscoped": 100.0}[bucket],
        top_ops=lambda bucket: []))
    got = read({"trace": object(), "chips": 4, "peaks": PEAKS})
    # 2 x 81.9 GB per chip at 819 GB/s is 0.2 s, against 0.3 s under the
    # gather's scope and 0.1 s that the program left unscoped
    assert got == {"value": pytest.approx(50.0), "gather_gb": 81.9,
                   "gather_ms": 300.0, "unscoped_ms": 100.0,
                   "bound": "hbm"}


def test_the_reader_gives_nothing_without_trace_counter_or_time(
        record, monkeypatch):
    facts = {"trace": None, "chips": 1, "peaks": PEAKS}
    trace.gauge("ttor.gather_bytes", 1e9)
    assert read(facts) is None                            # no trace
    gather_ms = {"gather": 0.0, "unscoped": 0.0}
    monkeypatch.setattr(scopes, "of", lambda facts: mock.Mock(
        per_solve_ms=lambda bucket: gather_ms[bucket],
        top_ops=lambda bucket: []))
    facts["trace"] = object()
    assert read(facts) is None                            # no gather time
    gather_ms["gather"] = 2.0
    assert read(facts)["value"] == pytest.approx(100 * 2e9 / 819e9 / 2e-3)
    trace.reset()
    assert read(facts) is None                            # no counter
    import repro.core

    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert read(facts) is None                            # no record
