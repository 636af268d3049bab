"""The scope buckets of ``exec_other_ms`` and the program's counters and
spans, read on recorded chip traces: from before the program had scopes
(``record_trace.py``), where all of it is unscoped, and after
(``record_scoped.py``: the unrolled and a scan lowering, on one chip and on
a 2x2 grid)."""

import gzip
import json
import sys
from pathlib import Path

import pytest

from bench import devtrace, registry, scopes
from bench.families import cholesky as fam
from repro.core import trace
from repro.linalg.cholesky import cholesky_graph

DATA = Path(__file__).resolve().parent / "data"
BUCKETS = ("gather", "scatter", "body", "exchange", scopes.UNSCOPED)
SCOPE_METRICS = {"exec_gather_ms": "gather", "exec_scatter_ms": "scatter",
                 "exec_body_ms": "body", "exec_exchange_ms": "exchange",
                 "exec_unscoped_ms": scopes.UNSCOPED}


@pytest.mark.parametrize("tf_op,bucket", [
    ("jit(run_unrolled)/ttor.gather/gather:", "gather"),
    ("jit(entry)/shard_map/while/body/ttor.body.gemm/vmap(jit(block_gemm))"
     "/pallas_call:", "body"),
    ("jit(entry)/shard_map/ttor.exchange/ppermute:", "exchange"),
    ("jit(a)/ttor.exchange/ttor.scatter/scatter:", "scatter"),
    ("jit(pack)/vmap()/gather:", scopes.UNSCOPED),
    ("jit(a)/not_ttor.gather/gather:", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_an_operation_goes_to_its_innermost_ttor_scope(tf_op, bucket):
    assert scopes.scope_of(tf_op) == bucket


# --------------------------------------------------- the wire reader

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, events) -> bytes:
    """An XPlane: ``stat_names`` {id: name}, ``events`` [(id, name,
    [(stat id, field, value)])]."""
    out = _field(1, 7) + _field(2, name)
    for eid, ename, stats in events:
        meta = _field(1, eid) + _field(2, ename) + _field(4, "short")
        for sid, field, value in stats:
            meta += _field(5, _field(1, sid) + _field(field, value))
        out += _field(4, _field(1, eid) + _field(2, meta))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    return out


def test_the_wire_reader_takes_each_events_tf_op():
    """A string ``tf_op`` and one by reference to a stat's name; events
    without one, and host planes, give nothing."""
    names = {3: "flops", 7: "tf_op", 9: "jit(f)/ttor.scatter/scatter:"}
    device = _plane("/device:TPU:0", names, [
        (1, "%a = f32[2] gather(...)", [(3, 4, 10),
                                         (7, 5, "jit(f)/ttor.gather/gather:")]),
        (2, "%b = f32[2] scatter(...)", [(7, 7, 9)]),
        (5, "%c = f32[2] copy(...)", [(3, 4, 1)])])
    host = _plane("/host:CPU", names, [(1, "python", [(7, 5, "x")])])
    xspace = _field(1, device) + _field(1, host) + _field(4, "host0")
    assert scopes.op_names(xspace) == {"/device:TPU:0": {
        "%a = f32[2] gather(...)": "jit(f)/ttor.gather/gather:",
        "%b = f32[2] scatter(...)": "jit(f)/ttor.scatter/scatter:"}}


def test_the_wire_reader_names_most_device_time_of_a_recorded_trace():
    """On the chip's own trace, the names the reader finds are those of the
    events ``ProfileData`` gives, and they cover most of the device time
    (the rest: operations XLA makes with no JAX op behind them)."""
    path = DATA / "chol_nb4_1chip.xplane.pb.gz"
    names = scopes.op_names(scopes.read_bytes(path))["/device:TPU:0"]
    events = devtrace.device_events(devtrace.load_file(path))[0]
    named = sum(e - s for text, s, e in events if text in names)
    assert set(names) <= {text for text, _, _ in events}
    assert named / sum(e - s for _, s, e in events) > 0.9


# ------------------------------------------------ recorded traces

def reading(tag: str):
    """(scope buckets, devtrace reduction, result line) of a recorded
    trace."""
    path = DATA / f"{tag}.xplane.pb.gz"
    red = devtrace.reduce(devtrace.load_file(path), fam.is_kernel,
                          fam.kernel_cost)
    result = json.loads((DATA / f"{tag}.result.json").read_text())
    return scopes.reduce(scopes.read_bytes(path)), red, result


def other_ms(red) -> float:
    facts = {"trace": red}
    return registry.metric_reader("exec_other_ms")(facts)


@pytest.mark.parametrize("tag", ["chol_nb4_1chip", "chol_nb4_4chip"])
def test_before_the_scopes_all_other_time_is_unscoped(tag):
    got, red, _ = reading(tag)
    assert {b for chip in got.bucket_ns.values() for b in chip} == \
        {scopes.UNSCOPED}
    assert got.per_solve_ms(scopes.UNSCOPED) == pytest.approx(other_ms(red))
    for bucket in ("gather", "scatter", "body", "exchange"):
        assert got.per_solve_ms(bucket) == 0.0


SCOPED = [(1, "unrolled"), (1, "scan"), (4, "unrolled"), (4, "scan")]


@pytest.fixture(scope="module", params=SCOPED,
                ids=[f"{c}chip_{lo}" for c, lo in SCOPED])
def scoped(request):
    chips, lowering = request.param
    tag = f"chol_nb4_{chips}chip_{lowering}"
    return (chips, tag) + reading(tag)


def test_the_scopes_partition_the_executors_other_time(scoped):
    chips, _, got, red, _ = scoped
    for bucket in ("gather", "scatter", "body"):
        assert got.per_solve_ms(bucket) > 0, bucket
    assert (got.per_solve_ms("exchange") > 0) == (chips > 1)
    assert {b for chip in got.bucket_ns.values() for b in chip} <= \
        set(BUCKETS)
    total = sum(got.per_solve_ms(b) for b in BUCKETS)
    assert total == pytest.approx(other_ms(red), rel=5e-3)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """Lays a recorded trace where ``run.py`` keeps the traced run's."""
    def lay(tag: str):
        where = tmp_path / tag / "plugins/profile/run"
        where.mkdir(parents=True)
        (where / "host.xplane.pb").write_bytes(
            gzip.decompress((DATA / f"{tag}.xplane.pb.gz").read_bytes()))
        monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / tag)
    return lay


def test_the_readers_give_what_the_chip_run_printed(scoped, trace_dir):
    chips, tag, _, red, result = scoped
    trace_dir(tag)
    facts = {"trace": red}
    printed = result["metrics"]
    for name, bucket in SCOPE_METRICS.items():
        if name not in printed:
            assert name == "exec_exchange_ms" and chips == 1
            continue
        got = registry.metric_reader(name)(facts)
        assert got["value"] == pytest.approx(printed[name]["value"])
        assert got["ops"] == printed[name]["ops"]
    gauges = result["program"]
    assert printed["exec_pad_ratio"]["value"] == pytest.approx(
        gauges["ttor.task_rows"] / gauges["ttor.tasks"])
    assert ("exchange_pad_ratio" in printed) == (chips > 1)


def test_the_counters_rows_run_the_kernel_flops_the_trace_counts(scoped):
    """syrk and gemm rows, each one b x b x b product in the kernel, times
    2 b^3, equal the flops the trace counts from the kernel calls' shapes,
    per solve."""
    _, _, _, red, result = scoped
    rows = result["program"]["ttor.task_rows_by_type"]
    b = 1024
    traced = sum(c.kernel_flops for c in red.chips.values()) / red.solves
    assert (rows["syrk"] + rows["gemm"]) * 2 * b ** 3 == \
        pytest.approx(traced, rel=1e-12)
    assert result["metrics"]["exec_pad_ratio"]["kernel_flops"] == \
        pytest.approx(traced)


def test_the_scope_reduction_is_read_once_per_run(trace_dir):
    trace_dir("chol_nb4_1chip")
    facts = {"trace": object()}
    assert scopes.of(facts) is scopes.of(facts)
    assert scopes.of({"trace": None}) is None


# ------------------------------------- the program's counters and spans

@pytest.fixture
def record():
    trace.reset()
    yield
    trace.reset()


def test_the_span_readers_read_the_programs_host_spans(record):
    cholesky_graph(6, 2, 1, 8).to_program()
    spans = {s.name: s for s in trace.snapshot()["spans"]}
    for metric, name in (("derive_s", "ttor.derive"),
                         ("discover_s", "ttor.discover")):
        got = registry.metric_reader(metric)({"trace": None})
        span = spans[name]
        assert got == pytest.approx((span.end_ns - span.start_ns) * 1e-9)
        assert got > 0


def test_the_exchange_ratio_needs_something_to_cross(record):
    trace.gauge("ttor.wire_slots", 0)
    trace.gauge("ttor.wire_blocks", 0)
    assert registry.metric_reader("exchange_pad_ratio")({}) is None
    trace.gauge("ttor.wire_slots", 47)
    trace.gauge("ttor.wire_blocks", 4)
    assert registry.metric_reader("exchange_pad_ratio")({}) == 47 / 4


def test_a_program_without_the_record_gives_no_counters_or_spans(
        record, monkeypatch):
    """As on a program from before ``repro.core.trace``: the counter and
    span readers find nothing to read, and say so with None."""
    import repro.core

    cholesky_graph(4, 1, 1, 8).to_program()
    trace.gauge("ttor.tasks", 20)
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert scopes.program_record() is None
    for name in ("exec_pad_ratio", "exchange_pad_ratio", "derive_s",
                 "discover_s"):
        assert registry.metric_reader(name)({"trace": None}) is None, name
