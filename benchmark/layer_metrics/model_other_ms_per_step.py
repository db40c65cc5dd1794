"""Device self time of one step under ``dpwa.forward`` and under no name of
``benchmark/block_scopes.GROUPS``: the outer norms, the embedding, the
residual adds, and a loss that carries no name; forward, backward and
recomputed together, on the chip that sets the pace.  With the groups it sums
to ``forward_ms_per_step`` + ``backward_ms_per_step``."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "other")
