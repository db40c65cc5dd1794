"""Device self time of one step under ``dpwa.attn.eva``: the EVA attention
layers whole (``models/llama.EvaAttention``: ``wq wk wv`` with their adapters,
the turns to heads first and back, rope, the chunk summaries, the core and
``wo``), forward, backward and
recomputed together, on the chip that sets the pace.  The core's hand-written
gradient names ``dpwa.attn.eva.core`` and no mixer, so all three names make
the group (``benchmark/block_scopes.ms_per_step`` over this table)."""

LAYER = "EVA attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"eva_attn": (
    "dpwa.attn.eva", "dpwa.attn.eva.summaries", "dpwa.attn.eva.core",
)}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "eva_attn", GROUPS)
