"""Summed device time of the windowed attention kernels in one step, from the
trace: ``ops/eva.causal_attention`` with a window, whose two calls are named
``flash_attention_fwd_dpwa_window`` / ``flash_mha_bwd_dpwa_window`` (forward,
once more where a block is recomputed, and backward).  They lie under
``tracered.FLASH_KERNEL``'s two prefixes too, so ``attn_kernel_ms_per_step``
counts them beside the full layers' kernels."""

LAYER = "attention kernels"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"
# The windowed calls alone: the causal calls over the whole triangle end in
# ``_dpwa`` and a ``.N``, never in ``_window``.
WINDOW_KERNEL = r"^(flash_attention_fwd|flash_mha_bwd)_dpwa_window"


def seconds_per_step(trace, record):
    """Seconds a traced step in the windowed kernels; None where there is no
    trace, no traced step, or no such kernel in it."""
    from benchmark import tracered

    if trace is None or not record["traced_steps"]:
        return None
    seconds = tracered.kernel_seconds(trace, WINDOW_KERNEL)
    return None if not seconds else seconds / record["traced_steps"]


def reduce(trace, record):
    seconds = seconds_per_step(trace, record)
    return None if seconds is None else 1e3 * seconds
