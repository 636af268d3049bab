"""Executor: the task rows the chosen lowering computes per solve, summed
over the chips, trash rows included, over the tasks the graph has: the
program's ``ttor.task_rows`` / ``ttor.tasks`` counters, set when the
executor is lowered. ``task_rows`` gives the rows per task type; with a
trace, ``kernel_flops`` gives the kernel's flops per solve as the trace
counts them from its calls' shapes (summed over the chips), which the rows
of the types the kernel runs, times 2 b^3, should equal. Nothing to read
where the program keeps no counters."""

from bench import scopes


def read(facts):
    gauges = (scopes.program_record() or {}).get("gauges", {})
    if not gauges.get("ttor.tasks"):
        return None
    out = {"value": gauges["ttor.task_rows"] / gauges["ttor.tasks"],
           "task_rows": gauges["ttor.task_rows_by_type"]}
    trace = facts.get("trace")
    if trace is not None:
        out["kernel_flops"] = (sum(c.kernel_flops
                                   for c in trace.chips.values())
                               / trace.solves)
    return out
