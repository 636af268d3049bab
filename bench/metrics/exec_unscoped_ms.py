"""Executor: device time per solve of the operations that are neither the
kernel nor collectives and lie under no ``ttor.`` scope of the program
(copies XLA inserts, the benchmark's own pack and fingerprint), averaged
over the chips; ``ops`` names the three that took most."""

from bench import scopes


def read(facts):
    return scopes.bucket_metric(facts, scopes.UNSCOPED)
