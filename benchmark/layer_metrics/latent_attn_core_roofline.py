"""The latent-attention core's share of its roofline: the least time the chip
could take for what the core must do a step at the published head sizes (q
and k 192, v 128, half the square; ``benchmark/flops_latent.py``, handed over
as ``kernel_work["latent_attn_core"]``) over the time of the attention
kernels the trace shows, the recomputed forward included.  What the kernels
spend on heads padded to 256, on whole diagonal blocks and on the
recomputation reads as lost share.  At T 1024 the two bounds lie within a
few per cent of each other (compute above); below it the bound is bytes."""

LAYER = "latent attention"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes, tracered

    if trace is None or not record["traced_steps"]:
        return None
    seconds = tracered.kernel_seconds(trace, tracered.FLASH_KERNEL)
    if not seconds:
        return None
    return latent_scopes.roofline_share(
        record, "latent_attn_core", seconds / record["traced_steps"]
    )
