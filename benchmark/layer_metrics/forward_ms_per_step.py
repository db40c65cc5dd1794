"""Device self time of one step under ``jvp(dpwa.forward)``, the loss call's
forward pass, on the chip whose phases sum highest (``benchmark/scopes.py``)."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import scopes

    return scopes.phase_ms_per_step(trace, record, "forward")
