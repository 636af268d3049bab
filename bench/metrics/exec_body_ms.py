"""Executor: device time per solve of the task bodies other than the
kernel, the operations under the program's ``ttor.body.<type>`` scopes
(potrf, trsm, the subtraction of syrk and gemm, the kernel batch's layout
copies), averaged over the chips."""

from bench import scopes


def read(facts):
    return scopes.bucket_metric(facts, "body")
