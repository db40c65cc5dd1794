"""The expert matmuls' share of their roofline: the least time the chip could
take for what they must do a step (the larger of FLOPs / peak FLOP/s and
bytes / peak bytes/s, both from shapes: ``benchmark/flops_moe.py``, handed
over as ``kernel_work["moe_experts"]``) over the device time under
``dpwa.moe.experts``.  At the published widths and 8,192 tokens a step the
two bounds lie within a few per cent of each other (compute just above)."""

LAYER = "expert layer"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import flops, moe_scopes

    work = (record.get("kernel_work") or {}).get("moe_experts")
    if not work:
        return None
    seconds = moe_scopes.scope_seconds_per_step(trace, record, "experts")
    if not seconds:
        return None
    peak = flops.peak(record["device_kind"])
    floor = max(
        work["flops"] / peak["bf16_flops_per_s"],
        work["bytes"] / peak["hbm_bytes_per_s"],
    )
    return 100.0 * floor / seconds
