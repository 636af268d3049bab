"""Exchange: the block slots the chosen lowering's exchange ships per solve,
padding included, over the blocks that must cross: the program's
``ttor.wire_slots`` / ``ttor.wire_blocks`` counters, set when the executor
is lowered. Nothing to read where the program keeps no counters or nothing
crosses."""

from bench import scopes


def read(facts):
    gauges = (scopes.program_record() or {}).get("gauges", {})
    if not gauges.get("ttor.wire_blocks"):
        return None
    return gauges["ttor.wire_slots"] / gauges["ttor.wire_blocks"]
