"""The mixers' projections' share of their roofline: the least time the chip
could take for what a step's four projections a mixer must do
(``benchmark/ssm_parts.projections_required`` at the cell's shapes, the
configuration found through ``BENCHMARK.json`` by this metric's cells: the
frozen kernels forward and to the activations, the adapters three times; the
larger of FLOPs / peak FLOP/s and bytes / peak bytes/s; the FLOPs bound) over
the time under ``dpwa.ssm.proj`` the trace shows (``ssm_proj_ms_per_step``),
a recomputed block's second forward included: recomputation reads as lost
share."""

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import ssm_parts

    return ssm_parts.roofline(trace, record, "ssm_proj", "ssm_proj_roofline")
