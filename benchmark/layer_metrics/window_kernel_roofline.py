"""The sliding layers' attention cores' share of their roofline: the least
time the chip could take for what the band requires a step
(``benchmark/flops_window.window_core_required``, handed over as
``kernel_work["window_attention"]``: the band's pairs and no block's edge,
``QK^T`` and ``PV`` forward and five products backward, ``K V dK dV`` counted
grouped; the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s; the FLOPs
bound at T 4,096) over the windowed kernels' time the trace shows
(``window_kernel_ms_per_step``), a recomputed block's second forward
included.  What a block's edge computes and masks reads as lost share."""

LAYER = "attention kernels"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes
    from benchmark.layer_metrics import window_kernel_ms_per_step

    return latent_scopes.roofline_share(
        record, "window_attention",
        window_kernel_ms_per_step.seconds_per_step(trace, record),
    )
