"""``exec_indexed_share``: the share of the executor's task rows whose body
read its operands from the slot array by index, from the program's
counters. At the cells' own sizes the counters give the rows the kernel
runs (syrk + gemm) over all task rows, as the lowering builds them; a
program that keeps no ``ttor.indexed_rows`` counter gives nothing."""

import json
import sys

import pytest

import jax

from bench import registry, scopes
from repro.core import trace
from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.cholesky import cholesky_executor, cholesky_graph


@pytest.fixture
def record():
    trace.reset()
    yield
    trace.reset()


def read():
    return registry.metric_reader("exec_indexed_share")({"trace": None})


@pytest.mark.parametrize("config,rows", [
    ("cholesky_f32_b1024", (30_618, 32_887)),
    ("cholesky_f32_b1024_2x2", (95_232, 107_140)),
], ids=["chol_n28k", "chol_n32k_2x2"])
def test_the_cells_share_is_their_kernel_rows(record, config, rows):
    """The cell's program, lowered as the benchmark lowers it (no compile):
    syrk and gemm rows read by index, over every task row."""
    cfg = json.loads((registry.BENCH / "configs" / f"{config}.json")
                     .read_text())
    pr, pc = cfg["grid"]
    prog = cholesky_graph(cfg["nb"], pr, pc, cfg["b"]).to_program()
    mesh = jax.sharding.AbstractMesh((pr * pc,), ("shards",))
    cholesky_executor(prog, mesh, matmul=task_matmul)
    by_type = trace.snapshot()["gauges"]["ttor.task_rows_by_type"]
    got = read()
    assert (got["indexed_rows"], got["task_rows"]) == rows
    assert got["indexed_rows"] == by_type["syrk"] + by_type["gemm"]
    assert got["value"] == rows[0] / rows[1]


def test_a_program_without_the_counter_gives_nothing(record, monkeypatch):
    """The counters a program kept before bodies could read by index, and
    no record at all: the reader finds nothing to read."""
    trace.gauge("ttor.tasks", 20)
    trace.gauge("ttor.task_rows", 91)
    assert read() is None
    trace.gauge("ttor.indexed_rows", 0)
    assert read()["value"] == 0
    import repro.core

    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert scopes.program_record() is None
    assert read() is None
