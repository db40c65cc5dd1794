"""Device self time of one step under ``dpwa.mlp``: the dense SwiGLU
feed-forward layers (``models/llama.MLP`` as a layer's feed-forward:
``w_gate``, ``w_up``, ``silu x up``, ``w_down``, adapters), forward, backward
and recomputed together, on the chip that sets the pace
(``benchmark/block_scopes.py``).  The shared expert is the expert layer's."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "mlp")
