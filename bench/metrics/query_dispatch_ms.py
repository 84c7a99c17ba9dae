"""Median host time of a window request in the jitted call until it
returns, before the answer is ready (``aerialdb.query.dispatch`` span
inside its ``bench.query`` span), from the traced slice."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.host_ms(run, "bench.query", ("aerialdb.query.dispatch",))
