"""Reduces a profiler trace of the timed window to what the per-layer
metrics read: per chip, the union of the intervals in which a device
operation ran (busy), the kernel's events with their operations and bytes,
the collectives, and the rest; the host's ``bench.*`` spans; and the
breakdown the result line carries.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. Each event is named by the text of its HLO
instruction, which ``parse_instruction`` reads: the name, the opcode, the
result and operand shapes. Control flow (``while``, ``conditional``,
``call``) holds the operations of its body and is no operation itself.
Times are nanoseconds on the profiler's clock, which the host spans and the
device events share.
"""

from __future__ import annotations

import gzip
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

COLLECTIVES = ("all-to-all", "collective-permute", "all-reduce",
               "all-gather", "reduce-scatter", "collective-broadcast",
               "ragged-all-to-all", "send", "recv")
CONTROL_FLOW = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclass(frozen=True)
class HloOp:
    name: str
    opcode: str
    shape: Optional[Tuple[int, ...]]
    operand_shapes: Tuple[Tuple[int, ...], ...]

    @property
    def collective(self) -> bool:
        return self.opcode.startswith(COLLECTIVES)

    @property
    def label(self) -> str:
        shape = ("[" + ",".join(map(str, self.shape)) + "]"
                 if self.shape is not None else "(tuple)")
        return f"{self.name} {self.opcode} {shape}"


_SHAPE = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def _dims(text: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


def parse_instruction(line: str) -> Optional[HloOp]:
    """One HLO instruction's text (``%name = type opcode(...), ...``), or
    None where the line is no instruction."""
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    name, sep, rest = line.partition(" = ")
    if not sep or " " in name or not rest:
        return None
    if rest.startswith("("):                          # a tuple type
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_text, rest = rest[: end + 1], rest[end + 1:].lstrip()
    else:
        type_text, _, rest = rest.partition(" ")
    m = re.match(r"([a-z][\w\-]*)\(", rest)
    if not m:
        return None
    shape = _SHAPE.match(type_text)
    constraints = re.search(
        r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", rest)
    operands = tuple(_dims(s) for s in _SHAPE.findall(
        constraints.group(1))) if constraints else ()
    return HloOp(name=name.lstrip("%"), opcode=m.group(1),
                 shape=_dims(shape.group(1)) if shape else None,
                 operand_shapes=operands)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two unions."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``busy`` (a union) inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def load_file(path: Path):
    """An ``.xplane.pb``, or one gzipped (``.gz``)."""
    from jax.profiler import ProfileData

    if Path(path).suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    return ProfileData.from_file(str(path))


def load(trace_dir: Path):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(files[-1])


def device_events(profile) -> Dict[int, List[Tuple[str, float, float]]]:
    """{chip id: [(HLO instruction, start_ns, end_ns)]} from each TPU
    plane's ``XLA Ops`` line."""
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


def host_spans(profile) -> List[Tuple[str, float, float]]:
    """The benchmark's own ``bench.*`` spans on the host planes."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return sorted(spans, key=lambda s: s[1])


@dataclass
class Chip:
    busy_ns: float = 0.0
    kernel_ns: float = 0.0
    kernel_flops: float = 0.0
    kernel_bytes: float = 0.0
    collective_ns: float = 0.0
    collective_exposed_ns: float = 0.0
    other_ns: float = 0.0
    idle: List[Interval] = field(default_factory=list)


@dataclass
class Reduction:
    window_ns: float
    solves: int
    chips: Dict[int, Chip]
    op_ns: Dict[str, float]          # per operation's label, summed over chips
    spans: List[Tuple[str, float, float]]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds per chip) and
        the longest idle gaps, each named by the innermost host span it
        fell in."""
        n = max(len(self.chips), 1)
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        idle = []
        for chip_id, chip in sorted(self.chips.items()):
            for s, e in chip.idle:
                mid = (s + e) / 2
                inside = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
                what = (min(inside, key=lambda sp: sp[2] - sp[1])[0]
                        if inside else "no bench span")
                idle.append((f"{what} (TPU:{chip_id})", (e - s) * 1e-9))
        idle.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                "idle_gaps": [list(g) for g in idle[:top]]}


def reduce(profile, is_kernel: Callable[[HloOp], bool],
           kernel_cost: Callable[[tuple, tuple], Tuple[float, float]],
           ) -> Reduction:
    """Reduce the trace of a window whose solves are ``bench.solve`` spans;
    the window runs from the first solve's start to the last one's end."""
    spans = host_spans(profile)
    solves = [s for s in spans if s[0] == SPAN_PREFIX + "solve"]
    if not solves:
        raise ValueError("the trace holds no bench.solve span")
    lo, hi = solves[0][1], solves[-1][2]
    chips: Dict[int, Chip] = {}
    op_ns: Dict[str, float] = {}
    for chip_id, events in device_events(profile).items():
        events = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        kinds: Dict[str, List[Interval]] = {"kernel": [], "collective": [],
                                            "other": []}
        chip = Chip()
        for text, s, e in events:
            op = parse_instruction(text)
            if op is not None and op.opcode in CONTROL_FLOW:
                continue
            if op is not None and is_kernel(op):
                kind = "kernel"
                flops, nbytes = kernel_cost(op.shape, op.operand_shapes)
                chip.kernel_flops += flops
                chip.kernel_bytes += nbytes
                chip.kernel_ns += e - s
            elif op is not None and op.collective:
                kind = "collective"
            else:
                kind = "other"
            kinds[kind].append((s, e))
            label = op.label if op is not None else text[:80]
            op_ns[label] = op_ns.get(label, 0.0) + (e - s)
        busy = union([iv for ivs in kinds.values() for iv in ivs])
        if not busy:
            continue
        coll = union(kinds["collective"])
        compute = union(kinds["kernel"] + kinds["other"])
        chip.busy_ns = length(busy)
        chip.collective_ns = length(coll)
        chip.collective_exposed_ns = length(coll) - overlap(coll, compute)
        chip.other_ns = length(union(kinds["other"]))
        chip.idle = gaps(busy, lo, hi)
        chips[chip_id] = chip
    return Reduction(window_ns=hi - lo, solves=len(solves), chips=chips,
                     op_ns=op_ns, spans=spans)
