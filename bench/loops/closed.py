"""A closed loop: each caller starts its next solve when its last has ended.

Traffic files that name ``"loop": "closed"`` are driven here. One caller is
what a user factorising one matrix again and again sends; the window then
holds whole solves only.
"""

from __future__ import annotations

import time

import jax


def drive(traffic: dict, solve, seconds: float):
    """Solves back to back from one caller until the first solve that ends
    at or after ``seconds``; that solve is in the window. Returns what each
    solve returned and the window's length in seconds."""
    if traffic.get("callers") != 1:
        raise ValueError(f"a closed loop is driven with one caller; got "
                         f"{traffic}")
    results = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.solve"):
            results.append(solve())
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return results, elapsed
