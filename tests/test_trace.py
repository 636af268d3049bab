"""The program's tracing (``repro.core.trace``): host spans kept in memory
on the profiler's clock, counters set anew at each lowering and equal to a
recount from the program's tables, and device scopes that put every
operation of the executor's work under a ``ttor.`` name in its compiled
HLO."""

import collections
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import trace
from repro.kernels.block_gemm.ops import task_matmul
from repro.linalg.cholesky import cholesky_executor, cholesky_graph
from repro.linalg.gemm import gemm_2d_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_record():
    trace.reset()
    yield
    trace.reset()


# ------------------------------------------------------------ host spans

def test_a_span_records_name_start_end_and_parent():
    with trace.span("ttor.outer"):
        with trace.span("ttor.inner"):
            time.sleep(0.002)
    inner, outer = trace.snapshot()["spans"]
    assert (inner.name, inner.parent) == ("ttor.inner", "ttor.outer")
    assert (outer.name, outer.parent) == ("ttor.outer", None)
    assert outer.start_ns <= inner.start_ns < inner.end_ns <= outer.end_ns
    assert inner.end_ns - inner.start_ns >= 2_000_000


def test_a_spans_parent_is_on_its_own_thread():
    seen = []

    def worker():
        with trace.span("ttor.worker"):
            pass
        seen.append(True)

    with trace.span("ttor.main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive() and seen
    spans = {s.name: s for s in trace.snapshot()["spans"]}
    assert spans["ttor.worker"].parent is None


def test_a_span_that_raises_is_not_recorded_and_unwinds():
    with pytest.raises(RuntimeError):
        with trace.span("ttor.fails"):
            raise RuntimeError("boom")
    with trace.span("ttor.after"):
        pass
    (after,) = trace.snapshot()["spans"]
    assert (after.name, after.parent) == ("ttor.after", None)


def test_the_record_keeps_the_newest_spans_only():
    for i in range(trace.MAX_SPANS + 5):
        with trace.span(f"s{i}"):
            pass
    spans = trace.snapshot()["spans"]
    assert len(spans) == trace.MAX_SPANS
    assert spans[0].name == "s5" and spans[-1].name == f"s{trace.MAX_SPANS + 4}"


def test_a_span_lands_on_the_profilers_clock(tmp_path):
    """The span's ``TraceAnnotation`` in a CPU profiler trace starts within
    1 ms of the in-memory record: the trace's times run from its
    ``profile_start_time``, on the wall clock the record keeps."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("ttor.probe"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    profile = ProfileData.from_file(str(path))
    (start,) = [v for p in profile.planes for k, v in p.stats
                if k == "profile_start_time"]
    (event,) = [e for p in profile.planes if p.name.startswith("/host:")
                for line in p.lines for e in line.events
                if e.name == "ttor.probe"]
    (span,) = trace.snapshot()["spans"]
    assert abs(start + event.start_ns - span.start_ns) < 1e6
    assert abs(event.duration_ns - (span.end_ns - span.start_ns)) < 1e6


def test_a_graph_build_records_derivation_and_discovery():
    cholesky_graph(4, 2, 1, 8).to_program()
    names = [s.name for s in trace.snapshot()["spans"]]
    assert names == ["ttor.derive", "ttor.discover"]


# ---------------------------------------------------------------- counters

def one_shard_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("shards",))


def recount_rows(prog, lowering: str) -> dict:
    """Task rows per type per solve, counted from ``prog.tables`` alone, for
    one shard: the unrolled lowering runs each wavefront's own table; the
    scan over the one segment runs its head wavefront's table, then every
    later wavefront padded to the longest of them."""
    assert prog.spec.n_shards == 1
    rows = collections.Counter()
    tables = prog.tables
    if lowering == "scan":
        assert len(prog.segments(comm="auto")) == 1
        for t, (ops, _) in tables[0].items():
            rows[t] += ops.shape[1]
        for t in prog.types:
            tail = [tbl[t][0].shape[1] for tbl in tables[1:] if t in tbl]
            rows[t] += (len(tables) - 1) * max(tail, default=0)
    else:
        for tbl in tables:
            for t, (ops, _) in tbl.items():
                rows[t] += ops.shape[1]
    return {t: rows[t] for t in prog.types}


@pytest.mark.parametrize("lowering,cap,mode", [
    ("unrolled", 64, "unrolled"), ("scan", 2, "segmented_scan")])
def test_the_counters_equal_a_recount_from_the_tables(lowering, cap, mode):
    prog = cholesky_graph(4, 1, 1, 8).to_program()
    assert prog.plan_lowering(unroll_cap=cap)["mode"] == mode
    cholesky_executor(prog, one_shard_mesh(), unroll_cap=cap)
    got = trace.snapshot()["gauges"]
    rows = recount_rows(prog, lowering)
    assert got["ttor.task_rows_by_type"] == rows
    assert got["ttor.task_rows"] == sum(rows.values())
    assert got["ttor.tasks"] == 4 + 6 + 6 + 4 == prog.n_tasks
    assert got["ttor.wire_slots"] == got["ttor.wire_blocks"] == 0


def test_each_lowering_sets_the_counters_anew():
    mesh = one_shard_mesh()
    cholesky_executor(cholesky_graph(4, 1, 1, 8).to_program(), mesh,
                      unroll_cap=2)
    assert trace.snapshot()["gauges"]["ttor.task_rows"] == 91
    prog = gemm_2d_graph(2, 1, 1, 8).to_program()
    prog.auto_executor({"gemm": lambda c, a, b: c + a @ b}, mesh)
    got = trace.snapshot()["gauges"]
    assert set(got["ttor.task_rows_by_type"]) == set(prog.types)
    assert got["ttor.tasks"] == prog.n_tasks
    assert got["ttor.task_rows"] == sum(
        ops.shape[1] for tbl in prog.tables for ops, _ in tbl.values())


# ----------------------------------------------------------- device scopes

# the work whose every operation must carry a ttor. scope: by the JAX
# primitive that made it, and by its HLO opcode
WORK_PRIMITIVES = ("gather", "scatter", "triangular_solve", "cholesky",
                   "pallas_call", "ppermute", "all_to_all")
WORK_OPCODES = ("gather", "scatter", "custom-call", "all-to-all",
                "collective-permute")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def executed_ops(hlo: str):
    """(name, opcode, op_name) of the instructions that run as operations:
    those of every computation but the ones fusions and reductions call."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    inner = {m.group(1) for lines in comps.values() for line in lines
             if not re.search(r"\bcall\(", line)
             for m in re.finditer(r"(?:calls|to_apply)=%([\w.\-]+)", line)}
    for comp, lines in comps.items():
        if comp in inner:
            continue
        for line in lines:
            m = _INSTRUCTION.search(line)
            if m:
                op_name = re.search(r'op_name="([^"]*)"', line)
                yield (m.group(1), m.group(2),
                       op_name.group(1) if op_name else "")


def unscoped_work(hlo: str):
    """The executed operations of the executor's work with no ``ttor.``
    scope in their name."""
    out = []
    for name, opcode, op_name in executed_ops(hlo):
        primitive = op_name.rsplit("/", 1)[-1]
        work = (primitive in WORK_PRIMITIVES
                or opcode.startswith(WORK_OPCODES))
        if work and "ttor." not in op_name:
            out.append((name, opcode, op_name))
    return out


def scopes_in(hlo: str) -> set:
    return set(re.findall(r"ttor\.[\w.]+", hlo))


def compiled_hlo(nb, pr, pc, b, mesh, **policy) -> str:
    prog = cholesky_graph(nb, pr, pc, b).to_program()
    x = jax.ShapeDtypeStruct((pr * pc, prog.n_slots, b, b), jnp.float32,
                             sharding=NamedSharding(mesh, P("shards")))
    run = jax.jit(cholesky_executor(prog, mesh, matmul=task_matmul,
                                    **policy))
    return run.lower(x).compile().as_text()


def scoped_hlo_case(nb, pr, pc, b, mesh, **policy) -> str:
    """Compile and check one form; returns the scopes found."""
    hlo = compiled_hlo(nb, pr, pc, b, mesh, **policy)
    bad = unscoped_work(hlo)
    assert not bad, bad[:5]
    found = scopes_in(hlo)
    want = {"ttor.gather", "ttor.scatter", "ttor.body.potrf",
            "ttor.body.trsm", "ttor.body.syrk", "ttor.body.gemm"}
    assert want <= found, found
    return found


@pytest.mark.parametrize("cap", [64, 2], ids=["unrolled", "scan"])
def test_every_operation_of_the_executors_work_is_scoped(cap):
    found = scoped_hlo_case(4, 1, 1, 128, one_shard_mesh(), unroll_cap=cap)
    assert "ttor.exchange" not in found


def test_every_operation_of_the_union_cover_scan_is_scoped():
    """Four shards in the union-cover scan, on four host devices: a child
    process (``tests.multi_device_cases trace_scopes``)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "tests.multi_device_cases", "trace_scopes"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\nstdout:{proc.stdout}\nstderr:{proc.stderr}"
    assert "CASE trace_scopes OK" in proc.stdout, proc.stdout


def test_unscoped_work_is_found():
    """The check itself: an executor built outside the scoped helpers (a
    bare gather and scatter) is reported."""
    def bare(x, idx):
        return x.at[idx].set(x[idx] * 2)

    hlo = jax.jit(bare).lower(jnp.ones((8, 4)), jnp.arange(3)).compile() \
        .as_text()
    assert unscoped_work(hlo)
