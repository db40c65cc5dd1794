"""Device self time of one step under ``dpwa.attn.eva.core``: the EVA core
alone (``ops/eva.eva_attention``: the forward kernel, once more where a block
is recomputed, the backward kernel, and the row sums beside it), on the chip
that sets the pace (``benchmark/block_scopes.ms_per_step`` over this
table)."""

LAYER = "EVA attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"eva_core": ("dpwa.attn.eva.core",)}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "eva_core", GROUPS)
