"""Device self time of one step in the expert layers of a replica that holds
every expert, outside the cell the ``moe_*`` metrics list: ``dpwa.moe.route``
(router, sigmoid, the biased top-k, the sort, the row gathers, the combine) +
``dpwa.moe.experts`` (the grouped matmuls with their adapters), forward,
backward and recomputed together, on the chip that sets the pace
(``benchmark/block_scopes.ms_per_step`` over this table).  What
``moe_route_ms_per_step`` + ``moe_expert_ms_per_step`` read in the cell they
list (``tests/yardstick/test_yardstick_moe.py`` holds their lists to that cell
alone)."""

LAYER = "expert layer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"expert_layer": ("dpwa.moe.route", "dpwa.moe.experts")}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "expert_layer", GROUPS)
