"""Device self time of one step under ``dpwa.ssm``: the Mamba mixers whole
(projections with their adapters, convolution, inner norms, the selective
scan and the gate), forward, backward and recomputed together, on the chip
that sets the pace (``benchmark/ssm_scopes.py``)."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import ssm_scopes

    seconds = ssm_scopes.group_seconds_per_step(trace, record, "ssm_mixer")
    return None if seconds is None else 1e3 * seconds
