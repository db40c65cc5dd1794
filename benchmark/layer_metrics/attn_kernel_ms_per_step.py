"""Summed device time of the library flash-attention kernels (forward, dq,
dkv) in one step, from the trace."""

LAYER = "attention kernels"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import tracered

    if trace is None or not record["traced_steps"]:
        return None
    seconds = tracered.kernel_seconds(trace, tracered.FLASH_KERNEL)
    if seconds is None:
        return None
    return 1e3 * seconds / record["traced_steps"]
