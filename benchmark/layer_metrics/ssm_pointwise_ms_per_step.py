"""Device self time of one step under ``dpwa.ssm.conv``, ``dpwa.ssm.dt`` and
``dpwa.ssm.gate``: what a Mamba mixer does between its projections and around
its scan (the split of ``in_proj``'s product, the causal convolution and
silu; the three inner norms, ``dt_bias`` and softplus in float32; ``y *
silu(z)``), forward, backward and recomputed together, on the chip that sets
the pace (``benchmark/block_scopes.ms_per_step`` over
``benchmark/ssm_parts.GROUPS``).  Part of ``ssm_mixer_ms_per_step``, beside
``ssm_scan_ms_per_step``.  What XLA fuses into a neighbouring matmul leaves
these names' time."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import ssm_parts

    return ssm_parts.ms_per_step(trace, record, "ssm_pointwise")
