"""Names the executor's own device time by the program's scopes.

``exec_other_ms`` is one bucket: every device operation of the window that
is neither the kernel nor a collective. The program names its device work
with ``jax.named_scope`` (``repro.core.trace.scope``): ``ttor.gather``,
``ttor.body.<type>``, ``ttor.scatter`` and ``ttor.exchange``. The name
reaches the trace as the ``tf_op`` stat of each operation's event metadata
(``jit(...)/ttor.gather/gather:``), which ``jax.profiler.ProfileData`` does
not show, so a small reader of the protobuf wire format takes it from the
``.xplane.pb`` itself.

Each operation is classified as ``devtrace.reduce`` classifies it: control
flow is skipped, then the kernel, then collectives; every other operation
goes to the innermost ``ttor.`` scope in its name, or is unscoped (copies
XLA inserts, the benchmark's own pack and fingerprint). Each bucket is a
union per chip, averaged over the chips, per solve, as ``exec_other_ms``
is, in the window the ``bench.solve`` spans bound.
"""

from __future__ import annotations

import functools
import gzip
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import devtrace
from bench.families.cholesky import is_kernel
from bench.registry import ROOT

# where run.py keeps the traced run's profile
TRACE_DIR = ROOT / ".bench_trace"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?:^|/)ttor\.([A-Za-z_]\w*)")


# ------------------------------------------------ protobuf wire format

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field or a fixed-width one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, memoryview]:
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, memoryview(b""))


def op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane name: {event name: tf_op}} from an XSpace: each
    event's metadata name (the HLO instruction's text, which
    ``ProfileData`` gives as the event's name) and its ``tf_op`` stat (the
    JAX op name with its name stack).

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1:
            continue
        name, events, stat_meta = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(value)
            elif field == 5:
                key, meta = _map_entry(value)
                stat_meta[key] = bytes(
                    dict(_fields(meta)).get(2, b"")).decode()
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        tf_op = {k for k, v in stat_meta.items() if v == "tf_op"}
        names = out.setdefault(name, {})
        for entry in events:
            event, op_name = "", None
            for field, value in _fields(_map_entry(entry)[1]):
                if field == 2:
                    event = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in tf_op:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_meta.get(stat.get(7), ""))
            if op_name is not None:
                names[event] = op_name
    return out


# --------------------------------------------------------- reduction

def scope_of(tf_op: Optional[str]) -> str:
    """The bucket of an operation's ``tf_op``: its innermost ``ttor.``
    scope's first part (``ttor.body.gemm`` is ``body``), else unscoped."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


@dataclass
class Scopes:
    solves: int
    bucket_ns: Dict[int, Dict[str, float]]       # chip: {bucket: union}
    op_ns: Dict[str, Dict[str, float]]           # bucket: {op label: ns}

    def per_solve_ms(self, bucket: str) -> float:
        """A bucket's device time per solve, mean over the chips; 0.0 for
        a bucket with no operation (a measured zero)."""
        chips = self.bucket_ns
        total = sum(b.get(bucket, 0.0) for b in chips.values())
        return total / max(len(chips), 1) / self.solves * 1e-6

    def top_ops(self, bucket: str, top: int = 3) -> List[list]:
        """The bucket's operations that took most time, ms per solve per
        chip."""
        n = max(len(self.bucket_ns), 1) * self.solves
        ops = sorted(self.op_ns.get(bucket, {}).items(),
                     key=lambda kv: -kv[1])[:top]
        return [[label, ns / n * 1e-6] for label, ns in ops]


def reduce(xspace: bytes) -> Scopes:
    """The scope buckets of a trace whose window is bounded by its
    ``bench.solve`` spans."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(xspace)
    names = op_names(xspace)
    solves = [s for s in devtrace.host_spans(profile)
              if s[0] == devtrace.SPAN_PREFIX + "solve"]
    if not solves:
        raise ValueError("the trace holds no bench.solve span")
    lo, hi = solves[0][1], solves[-1][2]
    bucket_ns: Dict[int, Dict[str, float]] = {}
    op_ns: Dict[str, Dict[str, float]] = {}
    for chip_id, events in devtrace.device_events(profile).items():
        scope_names = names.get(f"/device:TPU:{chip_id}", {})
        busy = False
        buckets: Dict[str, List[devtrace.Interval]] = {}
        for text, s, e in events:
            if not (e > lo and s < hi):
                continue
            s, e = max(s, lo), min(e, hi)
            op = devtrace.parse_instruction(text)
            if op is not None and op.opcode in devtrace.CONTROL_FLOW:
                continue
            busy = True
            if op is not None and (is_kernel(op) or op.collective):
                continue
            bucket = scope_of(scope_names.get(text))
            buckets.setdefault(bucket, []).append((s, e))
            label = op.label if op is not None else text[:80]
            got = op_ns.setdefault(bucket, {})
            got[label] = got.get(label, 0.0) + (e - s)
        if not busy:
            continue
        bucket_ns[chip_id] = {b: devtrace.length(devtrace.union(ivs))
                              for b, ivs in buckets.items()}
    return Scopes(solves=len(solves), bucket_ns=bucket_ns, op_ns=op_ns)


def read_bytes(path: Path) -> bytes:
    """An ``.xplane.pb``, or one gzipped (``.gz``)."""
    data = Path(path).read_bytes()
    return gzip.decompress(data) if Path(path).suffix == ".gz" else data


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> Scopes:
    return reduce(read_bytes(Path(path)))


def of(facts: dict) -> Optional[Scopes]:
    """The scope buckets of the run's traced window, read once per run;
    None in a run without a trace or without a chip in it."""
    if facts.get("trace") is None:
        return None
    files = sorted(Path(TRACE_DIR).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    got = _reduce_file(str(files[-1]), files[-1].stat().st_mtime_ns)
    return got if got.bucket_ns else None


def bucket_metric(facts: dict, bucket: str) -> Optional[dict]:
    """A scope metric's reading: the bucket's ms per solve and the three
    operations that took most of it; None in a run without a trace."""
    got = of(facts)
    if got is None:
        return None
    return {"value": got.per_solve_ms(bucket), "ops": got.top_ops(bucket)}


# ------------------------------------------- the program's own record

def program_record() -> Optional[dict]:
    """The spans and counters the program under test keeps in memory
    (``repro.core.trace.snapshot()``); None where it keeps none."""
    try:
        from repro.core import trace
    except ImportError:
        return None
    return trace.snapshot()


def span_s(name: str) -> Optional[float]:
    """Seconds in the program's host spans named ``name``; None where it
    records none."""
    record = program_record()
    got = [s for s in (record or {}).get("spans", ()) if s.name == name]
    if not got:
        return None
    return sum(s.end_ns - s.start_ns for s in got) * 1e-9
