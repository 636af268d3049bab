"""The chip benchmark: ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Everything that belongs to one configuration, traffic mix, graph family or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    configs/<file>.json      sizes, grid, dtype and precision, limits
    traffic/<traffic>.json   the load: its parameters, and the ``loop`` that
                             drives it
    loops/<loop>.py          ``drive(traffic, solve, seconds)``: one kind of
                             load (closed, open, bursts, ...)
    families/<family>.py     builds, solves and checks one graph family
    references/<family>.py   the plain reference that decides ``correct``
    metrics/<metric>.py      ``read(facts)``: one per-layer metric
    peaks.json               published peaks, keyed by ``device_kind``
"""
