"""Device: 1 - (union of the intervals in which an operation ran) / window,
averaged over the chips, in the traced window."""


def read(facts):
    trace = facts["trace"]
    if trace is None or not trace.chips:
        return None
    busy = [c.busy_ns for c in trace.chips.values()]
    return 100 * (1 - sum(busy) / len(busy) / trace.window_ns)
