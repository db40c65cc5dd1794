"""Device self time of one step under ``dpwa.moe.route``: router, softmax,
top-k, the sort of the assignments, the gathers into and out of expert order
and the weighted combine, forward and backward together, on the chip that
sets the pace (``benchmark/moe_scopes.py``)."""

LAYER = "expert layer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import moe_scopes

    seconds = moe_scopes.scope_seconds_per_step(trace, record, "route")
    return None if seconds is None else 1e3 * seconds
