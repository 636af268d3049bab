"""Executor lowering: ``jax.jit(executor).lower()`` traced to StableHLO, host
clock (never served from the compile cache)."""


def read(facts):
    return facts["lower_s"]
