"""The trace reduction, on intervals made by hand and on a recorded chip
trace (``record_trace.py``: nb 4 Cholesky on one TPU v5e)."""

import json
from pathlib import Path

import pytest

from bench import device, devtrace, registry
from bench.families import cholesky as fam

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlapping_and_touching_intervals():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7), (9, 9)]
    assert devtrace.length(devtrace.union([(0, 2), (1, 3)])) == 3


def test_overlap_of_two_unions():
    a = devtrace.union([(0, 10), (20, 30)])
    b = devtrace.union([(5, 25), (28, 40)])
    assert devtrace.overlap(a, b) == 5 + 5 + 2
    assert devtrace.overlap(a, []) == 0


def test_gaps_inside_a_window():
    busy = devtrace.union([(2, 4), (6, 9)])
    assert devtrace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert devtrace.gaps(busy, 3, 8) == [(4, 6)]
    assert devtrace.gaps([], 0, 1) == [(0, 1)]


KERNEL_CALL = (
    "%vmap_jit_block_gemm__.8 = f32[253,1024,1024]{2,1,0:T(8,128)} "
    "custom-call(f32[253,1024,1024]{2,1,0:T(8,128)} %copy.170, "
    "f32[253,1024,1024]{2,1,0:T(8,128)} %copy.172), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[253,1024,1024]{2,1,0}, f32[253,1024,1024]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_parse_instruction_reads_a_kernel_call_as_the_trace_names_it():
    op = devtrace.parse_instruction(KERNEL_CALL)
    assert op.name == "vmap_jit_block_gemm__.8" and fam.is_kernel(op)
    assert op.opcode == "custom-call" and not op.collective
    assert op.shape == (253, 1024, 1024)
    assert op.operand_shapes == ((253, 1024, 1024),) * 2
    assert op.label == "vmap_jit_block_gemm__.8 custom-call [253,1024,1024]"


@pytest.mark.parametrize("text,opcode,collective,shape", [
    ("%while.11 = (s32[]{:T(128)}, f32[601,1024,1024]{2,1,0}) "
     "while((s32[], f32[601,1024,1024]) %tuple.3), condition=%c, body=%b",
     "while", False, None),
    ("%collective-permute-start.2 = (f32[16,1024,1024]{2,1,0}, "
     "f32[16,1024,1024]{2,1,0}) collective-permute-start(f32[16,1024,1024]"
     "{2,1,0} %gather.1), source_target_pairs={{0,1},{1,0}}",
     "collective-permute-start", True, None),
    ("ROOT %all-to-all.1 = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %p)",
     "all-to-all", True, (4, 8)),
    ("%fusion.3 = f32[4,8,8]{2,1,0} fusion(f32[4,8,8]{2,1,0} %p), "
     "kind=kLoop", "fusion", False, (4, 8, 8)),
])
def test_parse_instruction_opcodes(text, opcode, collective, shape):
    op = devtrace.parse_instruction(text)
    assert (op.opcode, op.collective, op.shape) == (opcode, collective, shape)


@pytest.mark.parametrize("text", ["", "ENTRY %main (p: f32[4]) -> f32[4] {",
                                  "}", "jit_entry(4440341533424393300)"])
def test_parse_instruction_skips_what_is_no_instruction(text):
    assert devtrace.parse_instruction(text) is None


def recorded(chips):
    tag = f"chol_nb4_{chips}chip"
    reduction = devtrace.reduce(
        devtrace.load_file(DATA / f"{tag}.xplane.pb.gz"), fam.is_kernel,
        fam.kernel_cost)
    result = json.loads((DATA / f"{tag}.result.json").read_text())
    return reduction, result


@pytest.fixture(scope="module", params=[1, 4], ids=["one_chip", "2x2"])
def trace(request):
    return (request.param,) + recorded(request.param)


def test_the_recorded_window(trace):
    chips, red, result = trace
    assert red.solves == result["attempted"] >= 2
    assert len(red.chips) == chips
    assert red.window_ns * 1e-9 == pytest.approx(result["device"]["window_s"])
    for chip in red.chips.values():
        assert 0 < chip.kernel_ns < chip.busy_ns <= red.window_ns
        assert chip.other_ns > 0
        assert (chip.collective_ns > 0) == (chips > 1)
        assert chip.collective_exposed_ns <= chip.collective_ns


def test_the_kernel_runs_at_least_the_algorithms_updates(trace):
    """Per solve, the kernel's calls hold at least the syrk and gemm tasks
    of nb 4 (more where the lowering pads a wavefront's batch), and each
    call reads two blocks and writes one per 2 b^3 flops."""
    _, red, _ = trace
    b, counts = 1024, fam.task_counts(4)
    useful = (counts["syrk"] + counts["gemm"]) * 2 * b ** 3
    flops = sum(c.kernel_flops for c in red.chips.values())
    nbytes = sum(c.kernel_bytes for c in red.chips.values())
    assert flops / red.solves >= useful
    assert nbytes / flops == pytest.approx(12 * b * b / (2 * b ** 3))


def test_the_readers_give_what_the_chip_run_printed(trace):
    """The metrics the chip run printed come back from its recorded trace,
    read here on the CPU by the same readers."""
    chips, red, result = trace
    peaks = device.peaks_for("TPU v5 lite")
    facts = {"chips": chips, "peaks": peaks,
             "matmul_peak": device.matmul_peak(peaks, "highest"),
             "trace": red, "solves": red.solves,
             "solve_s": red.window_ns * 1e-9 / red.solves,
             "solve_flops": fam.solve_flops({"nb": 4, "b": 1024}),
             "host_setup_s": 0.0, "lower_s": 0.0}
    trace_metrics = ["exec_other_ms", "collective_exposed_ms",
                     "gemm_kernel_ms", "gemm_kernel_roofline",
                     "device_idle_share"]
    for name in trace_metrics:
        got = registry.metric_reader(name)(facts)
        if name == "collective_exposed_ms" and chips == 1:
            assert got is None
            continue
        value = got["value"] if isinstance(got, dict) else got
        assert value == pytest.approx(result["metrics"][name]["value"])
    assert 0 <= registry.metric_reader("device_idle_share")(facts) < 100


def test_the_kernel_roofline_is_against_the_six_pass_float32_peak(trace):
    """float32 at ``highest`` is 6 bf16 passes, so the kernel's compute
    ceiling is a sixth of the bf16 peak; the share stays under 100 on the
    recorded calls, and the line says which bound applies."""
    chips, red, _ = trace
    peaks = device.peaks_for("TPU v5 lite")
    facts = {"peaks": peaks, "trace": red,
             "matmul_peak": device.matmul_peak(peaks, "highest")}
    roof = registry.metric_reader("gemm_kernel_roofline")(facts)
    calls = red.chips.values()
    flops_s = sum(c.kernel_flops for c in calls) * 6 / 197e12
    bytes_s = sum(c.kernel_bytes for c in calls) / 819e9
    took_s = sum(c.kernel_ns for c in calls) * 1e-9
    assert roof["value"] == pytest.approx(100 * max(flops_s, bytes_s) / took_s)
    assert 0 < roof["value"] <= 100
    assert roof["bound"] == ("compute" if flops_s >= bytes_s else "hbm")


def test_no_kernel_in_the_trace_gives_no_roofline():
    facts = {"peaks": {}, "matmul_peak": 1.0, "trace": None}
    assert registry.metric_reader("gemm_kernel_roofline")(facts) is None


def test_the_breakdown(trace):
    _, red, result = trace
    got = red.breakdown()
    assert got == result["breakdown"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(got[key]) <= 10
        times = [t for _, t in got[key]]
        assert times == sorted(times, reverse=True)
    assert any(fam.KERNEL in name for name, _ in got["device_ops"])
