"""Device self time of one step under ``dpwa.attn.gqa``: plain attention
whole (``models/llama.Attention``: ``wq wk wv`` with their adapters, the q and
k norms where the configuration has them, rope, the core with its flash
kernels, ``wo``), forward, backward and recomputed together, on the chip that
sets the pace (``benchmark/block_scopes.py``).  Latent attention is
``latent_attn_ms_per_step``'s."""

LAYER = "attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "attn_gqa")
