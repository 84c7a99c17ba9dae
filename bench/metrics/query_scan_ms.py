"""Median device time of a window request in the log scan (``query.scan``
scope, the jnp engine or the Pallas kernel), on the chip that took
longest, inside the request's ``bench.query`` span."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.per_request_ms(run, ("query.scan",))
