"""Executor: device time per solve of the write-backs, the operations under
the program's ``ttor.scatter`` scope (task results into their slots),
averaged over the chips."""

from bench import scopes


def read(facts):
    return scopes.bucket_metric(facts, "scatter")
