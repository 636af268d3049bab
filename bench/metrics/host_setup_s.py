"""Host set-up: ``Graph.to_program()`` (the graph's derivation, discovery
and ``build_block_program``), host clock."""


def read(facts):
    return facts["host_setup_s"]
