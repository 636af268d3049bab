"""Executor: device time per solve of the operand gathers, the operations
under the program's ``ttor.gather`` scope (the wavefront tables' operand
blocks, padding included), averaged over the chips."""

from bench import scopes


def read(facts):
    return scopes.bucket_metric(facts, "gather")
