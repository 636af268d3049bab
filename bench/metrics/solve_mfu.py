"""Whole solve: the algorithm's flops per solve (N^3/3 for Cholesky, the
same whatever implements it) over ``solve_s``, over the chips' bf16 peak."""


def read(facts):
    peak = facts["chips"] * facts["peaks"]["bf16_flops_per_s"]
    return 100 * facts["solve_flops"] / facts["solve_s"] / peak
