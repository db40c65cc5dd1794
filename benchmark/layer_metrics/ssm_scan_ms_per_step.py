"""Device self time of one step under ``dpwa.ssm.scan``: the selective scans
alone (``ops/ssm.py``: the forward kernel, once more where a block is
recomputed, the backward kernel, and the transposes and sums around them),
on the chip that sets the pace (``benchmark/ssm_scopes.py``)."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import ssm_scopes

    seconds = ssm_scopes.group_seconds_per_step(trace, record, "ssm_scan")
    return None if seconds is None else 1e3 * seconds
