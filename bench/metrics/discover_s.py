"""Host set-up: the distributed discovery of the schedule in
``build_block_program`` (``discover_local`` / ``discover``), the program's
``ttor.discover`` span."""

from bench import scopes


def read(facts):
    return scopes.span_s("ttor.discover")
