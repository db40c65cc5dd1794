"""Device self time of one step under ``dpwa.ssm.proj``: the Mamba mixers'
four projections (``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj``) with
their adapters, forward, backward and recomputed together, on the chip that
sets the pace (``benchmark/block_scopes.ms_per_step`` over
``benchmark/ssm_parts.GROUPS``).  Part of ``ssm_mixer_ms_per_step``, beside
``ssm_scan_ms_per_step``.  A fusion is booked whole to the name of its own
``op_name``: what XLA fuses of a pointwise pass into a projection's matmul
reads here."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import ssm_parts

    return ssm_parts.ms_per_step(trace, record, "ssm_proj")
