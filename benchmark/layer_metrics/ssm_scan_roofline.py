"""The selective scan's share of its roofline: the least time the chip could
take for what a step's scans must do (``benchmark/flops_ssm.py``, handed over
as ``kernel_work["selective_scan"]``: the larger of FLOPs / peak FLOP/s and
bytes / peak bytes/s; the bytes bound) over the time under ``dpwa.ssm.scan``
the trace shows, the recomputed forward and a chunk's recomputation included.
Neither peak is the vector unit's: the recurrence is elementwise work the MXU
cannot take, and the chip's 197 TFLOP/s are the MXU's, so a scan that kept the
vector unit full would still read far under 100 here.  The share says how far
the scan is from costing only its memory traffic."""

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes, ssm_scopes

    seconds = ssm_scopes.group_seconds_per_step(trace, record, "ssm_scan")
    return latent_scopes.roofline_share(record, "selective_scan", seconds)
