"""Median host time of a window request in the federated query's jitted
call until it returns, before the answer is ready (``aerialdb.fed.call``
span inside its ``bench.query`` span), from the traced slice. None where
the program opens no such span: a one-device run, or a program that does
not name it."""
from bench import scopes

UNIT = "ms"


def read(run):
    return scopes.host_ms(run, "bench.query", ("aerialdb.fed.call",))
