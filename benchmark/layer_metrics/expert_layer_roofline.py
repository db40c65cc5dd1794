"""The expert matmuls' share of their roofline where an expert's width is the
configuration's ``moe_intermediate_size``: the least time the chip could take
for what they must do a step (``benchmark/flops_lfm2.expert_layer_required``
= ``flops_moe.moe_experts_required`` of the expert layers, handed over as
``kernel_work["expert_layer"]``: the larger of FLOPs / peak FLOP/s and bytes
/ peak bytes/s; at 32 experts of 1,792 and 8,192 tokens a step the FLOPs
bound, 30.0 ms against 25.5) over the device time under ``dpwa.moe.experts``
(``benchmark/block_scopes.ms_per_step`` over this table)."""

LAYER = "expert layer"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"experts": ("dpwa.moe.experts",)}


def reduce(trace, record):
    from benchmark import block_scopes, latent_scopes

    ms = block_scopes.ms_per_step(trace, record, "experts", GROUPS)
    return latent_scopes.roofline_share(
        record, "expert_layer", None if ms is None else 1e-3 * ms
    )
