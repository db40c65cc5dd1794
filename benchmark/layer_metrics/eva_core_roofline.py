"""The EVA core's share of its roofline: the least time the chip could take
for what a step's cores must do (``benchmark/flops_eva.eva_core_required``,
handed over as ``kernel_work["eva_attention"]``: the larger of FLOPs / peak
FLOP/s and bytes / peak bytes/s; the FLOPs bound) over the time under
``dpwa.attn.eva.core`` the trace shows, a recomputed block's second forward
included."""

LAYER = "EVA attention"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes
    from benchmark.layer_metrics import eva_core_ms_per_step

    ms = eva_core_ms_per_step.reduce(trace, record)
    return latent_scopes.roofline_share(
        record, "eva_attention", None if ms is None else 1e-3 * ms
    )
