"""Device self time of one step under ``dpwa.head``: the projection to the
vocabulary (``lm_head``, or ``x E^T`` where the embedding is tied) with its
float32 logits, forward and backward together, on the chip that sets the pace
(``benchmark/block_scopes.py``)."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "head")
