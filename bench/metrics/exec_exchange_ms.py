"""Exchange: device time per solve of the exchange's own operations other
than the collectives, under the program's ``ttor.exchange`` scope (send
rows gathered, arrivals landed in their slots), averaged over the chips."""

from bench import scopes


def read(facts):
    return scopes.bucket_metric(facts, "exchange")
