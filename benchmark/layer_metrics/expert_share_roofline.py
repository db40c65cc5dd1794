"""The held experts' grouped matmuls' share of their roofline: the least time
the chip could take for what they must do a step (``benchmark/flops_latent.py``,
handed over as ``kernel_work["held_experts"]``: every held expert's weights
read once a pass, forward and to the activations, and the expected rows) over
the device time under ``dpwa.moe.experts``.  With 1/24 of a deployment's rows
the bound is reading the weights; the recomputed forward reads them a third
time, which is not required work."""

LAYER = "expert layer"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes, moe_scopes

    return latent_scopes.roofline_share(
        record, "held_experts",
        moe_scopes.scope_seconds_per_step(trace, record, "experts"),
    )
