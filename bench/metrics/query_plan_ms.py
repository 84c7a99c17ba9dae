"""Median device time of a window request in planning and the per-edge
OR-list build (``query.plan`` + ``query.orlist`` scopes), on the chip that
took longest, inside the request's ``bench.query`` span."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.per_request_ms(run, ("query.plan", "query.orlist"))
