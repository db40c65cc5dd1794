"""Device self time of one step under ``dpwa.optimizer`` (``optimizer.update``
and ``apply_updates``), on the chip whose phases sum highest
(``benchmark/scopes.py``).  A fusion is booked whole to the scope of its own
name, so an update fused into a backward matmul reads as backward."""

LAYER = "step builders"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import scopes

    return scopes.phase_ms_per_step(trace, record, "optimizer")
