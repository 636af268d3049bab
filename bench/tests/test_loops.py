"""Traffic files name the loop that drives them; loops are found by name."""

import json
import time

import pytest

from bench import registry


def test_each_traffic_file_names_a_loop_that_drives_it():
    for path in sorted((registry.BENCH / "traffic").glob("*.json")):
        traffic = json.loads(path.read_text())
        assert callable(registry.loop(traffic)), path.name


def test_an_unknown_loop_is_an_error():
    with pytest.raises(KeyError, match="no bench/loops/open_zipf.py"):
        registry.loop({"loop": "open_zipf"})


def test_the_closed_loop_ends_on_the_first_solve_past_the_window():
    calls = []

    def solve():
        calls.append(time.perf_counter())
        time.sleep(0.02)
        return len(calls)

    drive = registry.loop({"loop": "closed"})
    got, window_s = drive({"loop": "closed", "callers": 1}, solve, 0.05)
    assert got == list(range(1, len(calls) + 1))
    assert window_s >= 0.05 and len(got) >= 3
    with pytest.raises(ValueError, match="one caller"):
        drive({"loop": "closed", "callers": 2}, solve, 0.05)
