"""Device self time of one step under ``dpwa.attn.window``: the
sliding-window attention layers whole (``models/llama.Attention`` of kind
``sliding_attention``: ``wq wk wv`` with their adapters, the norms a head, the
layer's own rope, the windowed core with its kernels, ``wo``), forward,
backward and recomputed together, on the chip that sets the pace
(``benchmark/block_scopes.ms_per_step`` over this table).  The name lies
inside ``dpwa.attn.gqa``, so this time is part of ``attn_ms_per_step``; the
full-attention layers are the rest of it."""

LAYER = "attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"attn_window": ("dpwa.attn.window",)}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "attn_window", GROUPS)
