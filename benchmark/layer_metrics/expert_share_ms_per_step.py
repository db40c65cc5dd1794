"""Device self time of one step in the expert layer of a replica that holds a
share of the experts: ``dpwa.moe.route`` (router, sigmoid, top-k, the sort,
the row gathers, the combine) + ``dpwa.moe.experts`` (the held experts'
grouped matmuls with their adapters) + ``dpwa.moe.shared`` (the shared
expert), forward, backward and recomputed together, on the chip that sets
the pace (``benchmark/latent_scopes.py``).  The first two are what
``moe_route_ms_per_step`` and ``moe_expert_ms_per_step`` read in the cell
they list (``tests/yardstick/test_yardstick_moe.py`` holds their lists to
that cell alone)."""

LAYER = "expert layer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes

    seconds = latent_scopes.group_seconds_per_step(trace, record, "expert_share")
    return None if seconds is None else 1e3 * seconds
