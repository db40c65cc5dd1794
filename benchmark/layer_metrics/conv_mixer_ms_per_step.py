"""Device self time of one step under ``dpwa.conv``: the gated short
convolutions whole (``models/llama.ShortConv``: ``in_proj`` and ``out_proj``
with their adapters, ``b * u``, the taps and ``c * z`` between them), forward,
backward and recomputed together, on the chip that sets the pace.  Both names
make the group, the gate's lying inside the mixer's
(``benchmark/block_scopes.ms_per_step`` over this table)."""

LAYER = "short convolution"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"conv_mixer": ("dpwa.conv", "dpwa.conv.gate")}


def reduce(trace, record):
    from benchmark import block_scopes

    return block_scopes.ms_per_step(trace, record, "conv_mixer", GROUPS)
