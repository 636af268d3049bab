"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(name: str, spec: dict | None = None) -> dict:
    """The cell's ``BENCHMARK.json`` entry with its configuration and
    traffic loaded, and the per-layer metrics it reports."""
    spec = spec or benchmark()
    entry = _named(spec["workloads"], name, "workload")
    conf = _named(spec["configs"], entry["config"], "config")
    traffic = json.loads(
        (BENCH / "traffic" / f"{entry['traffic']}.json").read_text())
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {**entry, "config_data": json.loads((ROOT / conf["file"])
                                               .read_text()),
            "traffic_data": traffic, "per_layer": per_layer,
            "end_to_end": end_to_end}


def family(name: str):
    return importlib.import_module(f"bench.families.{name}")


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded from its file."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(facts)`` of ``metrics/<name>.py``."""
    return _module("metrics", name).read


def loop(traffic: dict):
    """The ``drive(traffic, solve, seconds)`` of ``loops/<loop>.py``, for a
    traffic file that names its ``loop``."""
    return _module("loops", traffic["loop"]).drive
