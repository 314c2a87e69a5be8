"""Mean host time of a decode step of every active slot in the
closed-loop cells: the harness span around ``ServingEngine._decode_step``."""
from lib.readers import span_ms


def read(record):
    return span_ms(record, "step", "closed")
