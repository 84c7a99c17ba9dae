"""Median device time of a window request in the candidate exchange
between chips (``query.exchange`` scope: the all-gathers of the federated
query's candidate merge), on the chip that took longest, inside the
request's ``bench.query`` span. None where no op carries the scope: a
one-device run, or a program that does not name it."""
from bench import scopes

UNIT = "ms"


def read(run):
    s = scopes.of(run)
    if s is None or "query.exchange" not in s.scope_busy.get("bench.query",
                                                             {}):
        return None
    return scopes.per_request_ms(run, ("query.exchange",))
