"""Median host time of a window request in building its predicate and
preparing the call (``aerialdb.make_pred`` + ``aerialdb.query.prepare``
spans inside its ``bench.query`` span), from the traced slice."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.host_ms(run, "bench.query",
                          ("aerialdb.make_pred", "aerialdb.query.prepare"))
