"""Exchange: collective time per solve during which no other operation ran
on that chip, on the chip where it is largest. Nothing to read where no
collective ran."""


def read(facts):
    trace = facts["trace"]
    if trace is None or not any(c.collective_ns for c in trace.chips.values()):
        return None
    worst = max(c.collective_exposed_ns for c in trace.chips.values())
    return worst / trace.solves * 1e-6
