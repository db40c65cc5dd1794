"""``transport.exchange`` alone on the live tree's exchanged leaves, after
the window (run.py ``time_exchange_alone``)."""

LAYER = "exchange"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "host_clock"


def reduce(trace, record):
    return record.get("exchange_alone_ms")
