"""Device self time of one step under ``dpwa.attn.eva.summaries``: the chunk
summaries alone (``ops/eva.chunk_summaries``: a chunk's softmax and the two
pooled sums, with the gradient JAX derives), forward, backward and recomputed
together, on the chip that sets the pace
(``benchmark/block_scopes.ms_per_step`` over this table)."""

LAYER = "EVA attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"eva_summaries": ("dpwa.attn.eva.summaries",)}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "eva_summaries", GROUPS)
