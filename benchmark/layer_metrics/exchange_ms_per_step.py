"""Device self time of one step under ``dpwa.exchange`` (pairing, draws,
alpha, ``ppermute`` or gather, merge), on the chip whose phases sum highest
(``benchmark/scopes.py``)."""

LAYER = "exchange"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import scopes

    return scopes.phase_ms_per_step(trace, record, "exchange")
