"""Executor: device time per solve of the operations that are neither the
kernel nor collectives (gathers and scatters of the wavefront tables,
padding, the potrf/trsm bodies), averaged over the chips."""


def read(facts):
    trace = facts["trace"]
    if trace is None or not trace.chips:
        return None
    per_chip = [c.other_ns for c in trace.chips.values()]
    return sum(per_chip) / len(per_chip) / trace.solves * 1e-6
