"""Host set-up: the graph's per-shard derivation (``Graph.local_views``,
each shard's ``derive_local``), the program's ``ttor.derive`` span."""

from bench import scopes


def read(facts):
    return scopes.span_s("ttor.derive")
