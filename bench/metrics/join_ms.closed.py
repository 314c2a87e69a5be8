"""Mean host time of a join (prefill, slot merge, first-token fetch) in
the closed-loop cells: the harness span around ``ServingEngine._join``."""
from lib.readers import span_ms


def read(record):
    return span_ms(record, "join", "closed")
