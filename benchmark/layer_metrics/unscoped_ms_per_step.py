"""Device self time of one step under none of the program's scopes (the
clock, the step counter, state packing, and the copies and slices the compiler
adds), on the chip whose phases sum highest (``benchmark/scopes.py``)."""

LAYER = "step builders"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import scopes

    return scopes.phase_ms_per_step(trace, record, "unscoped")
