"""Kernel: device time per solve of the ``block_gemm`` kernel's events,
averaged over the chips."""


def read(facts):
    trace = facts["trace"]
    if trace is None or not any(c.kernel_ns for c in trace.chips.values()):
        return None
    per_chip = [c.kernel_ns for c in trace.chips.values()]
    return sum(per_chip) / len(per_chip) / trace.solves * 1e-6
