"""The families' operation and byte counts against the graph's own tasks."""

import numpy as np
import pytest

from bench.families import cholesky as fam
from repro.linalg.cholesky import cholesky_graph


def program_task_counts(nb, pr, pc, b=8):
    """Tasks of each type that the compiled program runs (its real,
    non-trash table rows), over every wavefront and shard."""
    prog = cholesky_graph(nb, pr, pc, b).to_program()
    counts = {}
    for tables in prog.tables:
        for t, (_, out) in tables.items():
            counts[t] = counts.get(t, 0) + int((out != prog.trash).sum())
    return counts


@pytest.mark.parametrize("nb,pr,pc", [(3, 1, 1), (6, 1, 1), (9, 2, 2),
                                      (7, 2, 1)])
def test_task_counts_match_the_program(nb, pr, pc):
    assert program_task_counts(nb, pr, pc) == fam.task_counts(nb)


@pytest.mark.parametrize("nb", [1, 2, 5, 24, 32])
def test_kernel_tasks_are_syrk_plus_gemm(nb):
    c = fam.task_counts(nb)
    assert (c["syrk"] + c["gemm"]
            == nb * (nb - 1) // 2 + nb * (nb - 1) * (nb - 2) // 6)


@pytest.mark.parametrize("nb,b", [(1, 4), (6, 8), (24, 1024), (32, 1024)])
def test_task_flops_sum_to_n_cubed_over_three(nb, b):
    per_task = fam.task_flops(b)
    total = sum(n * per_task[t] for t, n in fam.task_counts(nb).items())
    config = {"nb": nb, "b": b}
    assert total == pytest.approx(fam.solve_flops(config), rel=1e-12)
    assert fam.solve_flops(config) == pytest.approx((nb * b) ** 3 / 3)


def test_kernel_cost_of_a_batched_call():
    """C[t] = A[t] B[t] for 253 tasks of 1024-wide f32 blocks: 2 t b^3
    flops, and A, B read and C written once."""
    t, b = 253, 1024
    flops, nbytes = fam.kernel_cost((t, b, b), ((t, b, b), (t, b, b)))
    assert flops == 2 * t * b ** 3
    assert nbytes == 4 * 3 * t * b * b


def test_kernel_cost_of_an_unbatched_rectangular_call():
    flops, nbytes = fam.kernel_cost((8, 16), ((8, 4), (4, 16)))
    assert flops == 2 * 8 * 16 * 4
    assert nbytes == 4 * (8 * 4 + 4 * 16 + 8 * 16)


@pytest.mark.parametrize("nb,pr,pc", [(5, 1, 1), (6, 2, 2)])
def test_user_blocks_cover_the_lower_triangle_once(nb, pr, pc):
    coords = fam.owned_lower_blocks(nb, pr, pc)
    real = [tuple(c) for s in coords for c in s if c[0] < nb]
    assert sorted(real) == [(i, j) for i in range(nb) for j in range(i + 1)]
    for s, blocks in enumerate(coords):
        for i, j in blocks:
            if i < nb:
                assert (i % pr) * pc + (j % pc) == s


@pytest.mark.parametrize("nb,pr,pc", [(5, 1, 1), (6, 2, 2)])
def test_slots_follow_the_programs_slot_of(nb, pr, pc):
    prog = cholesky_graph(nb, pr, pc, 8).to_program()
    coords = fam.owned_lower_blocks(nb, pr, pc)
    idx, mask = fam.slot_gather(prog, coords)
    a_blocks = {k: v for k, v in prog.slot_of.items() if k[0] == "A"}
    assert mask.sum() == len(a_blocks) == nb * (nb + 1) // 2
    for (_, i, j), (s, slot) in a_blocks.items():
        assert mask[s, slot] and tuple(coords[s, idx[s, slot]]) == (i, j)
    slots, l_coords = fam.factor_slots(prog, nb)
    for s in range(prog.spec.n_shards):
        for slot, (i, j) in zip(slots[s], l_coords[s]):
            if i < nb:
                assert prog.slot_of[("L", int(i), int(j))] == (s, slot)
    assert int((l_coords[..., 0] < nb).sum()) == nb * (nb + 1) // 2
    assert np.all(l_coords[..., 0] <= nb)

