"""Executor: the least time the chip could take for the operand gathers of
a solve, over the time they took. The least time is the program's
``ttor.gather_bytes`` counter (the blocks the chosen lowering's gathers copy
per solve, summed over the chips) per chip, read once and written once, over
the HBM bandwidth. The time taken is the device time per solve under the
program's ``ttor.gather`` scope (``exec_gather_ms``) plus the time the
program leaves unscoped (``exec_unscoped_ms``): on a TPU the gather's last
step, which joins the row pieces it gathered (``pad_maximum_fusion``), has
no op name and lands there, beside small copies and the benchmark's pack.
So an unnamed step can only lower the share, never lift it over 100%.
``gather_gb`` gives the counted gigabytes per solve per chip. Nothing to
read without a trace, without the counter, or with no time under the
scope."""

from bench import scopes


def read(facts):
    gauges = (scopes.program_record() or {}).get("gauges", {})
    if "ttor.gather_bytes" not in gauges:
        return None
    gather = scopes.bucket_metric(facts, "gather")
    if gather is None or gather["value"] <= 0:
        return None
    unscoped = scopes.bucket_metric(facts, scopes.UNSCOPED)["value"]
    per_chip = gauges["ttor.gather_bytes"] / facts["chips"]
    least_s = 2 * per_chip / facts["peaks"]["hbm_bytes_per_s"]
    took_s = (gather["value"] + unscoped) * 1e-3
    return {"value": 100 * least_s / took_s, "gather_gb": per_chip * 1e-9,
            "gather_ms": gather["value"], "unscoped_ms": unscoped,
            "bound": "hbm"}
