"""Median device time of a window request in the index lookup and the
candidate merge (``query.lookup`` + ``query.merge`` scopes), on the chip
that took longest, inside the request's ``bench.query`` span."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.per_request_ms(run, ("query.lookup", "query.merge"))
