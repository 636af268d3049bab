"""Executor: the share of the chosen lowering's task rows whose body read
its operand blocks from the slot array by index, in place of the executor's
gather: the program's ``ttor.indexed_rows`` / ``ttor.task_rows`` counters,
set when the executor is lowered, summed over the chips. Nothing to read
where the program keeps no such counter."""

from bench import scopes


def read(facts):
    gauges = (scopes.program_record() or {}).get("gauges", {})
    if "ttor.indexed_rows" not in gauges or not gauges.get("ttor.task_rows"):
        return None
    return {"value": gauges["ttor.indexed_rows"] / gauges["ttor.task_rows"],
            "indexed_rows": gauges["ttor.indexed_rows"],
            "task_rows": gauges["ttor.task_rows"]}
