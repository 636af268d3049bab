"""Kernel: the least time the chip could take for the ``block_gemm`` calls
of the window, over the time they took. The least time is the larger of
their operations over the chip's peak for matmuls at the configuration's
precision (float32 at ``highest`` is 6 bf16 passes, so 1/6 of the bf16
peak) and their bytes over the HBM bandwidth, from each call's shapes.
``bound`` says which of the two it is."""


def read(facts):
    trace = facts["trace"]
    chips = [c for c in (trace.chips.values() if trace else ()) if c.kernel_ns]
    if not chips:
        return None
    flops_s = sum(c.kernel_flops for c in chips) / facts["matmul_peak"]
    bytes_s = (sum(c.kernel_bytes for c in chips)
               / facts["peaks"]["hbm_bytes_per_s"])
    took_s = sum(c.kernel_ns for c in chips) * 1e-9
    return {"value": 100 * max(flops_s, bytes_s) / took_s,
            "bound": "compute" if flops_s >= bytes_s else "hbm"}
