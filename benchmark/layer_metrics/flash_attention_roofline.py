"""The flash-attention kernels' share of their roofline: the least time the
chip could take for what they must do (the larger of FLOPs / peak FLOP/s and
bytes / peak bytes/s, both from shapes, benchmark/flops.py) over the time the
trace shows.  Causal attention does T/4 FLOPs a byte here, so from T 512 up
the bound is compute on a v5e (240 FLOPs a byte at the ridge is not reached
at T 512: there the bound is memory; the reader takes the larger either way)."""

LAYER = "attention kernels"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import flops, tracered

    work = (record.get("kernel_work") or {}).get("flash_attention")
    if trace is None or not work or not record["traced_steps"]:
        return None
    seconds = tracered.kernel_seconds(trace, tracered.FLASH_KERNEL)
    if not seconds:
        return None
    peak = flops.peak(record["device_kind"])
    floor = max(
        work["flops"] / peak["bf16_flops_per_s"],
        work["bytes"] / peak["hbm_bytes_per_s"],
    )
    return 100.0 * floor * record["traced_steps"] / seconds
