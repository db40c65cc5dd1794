"""Device self time of one step under ``dpwa.loss``: the cross-entropy over
the vocabulary and its gradient (``ops/cross_entropy.softmax_cross_entropy``
where a builder puts the name on), on the chip that sets the pace
(``benchmark/block_scopes.py``).  A builder whose loss carries no name reads
under ``model_other_ms_per_step``."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "loss")
