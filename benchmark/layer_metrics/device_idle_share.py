"""1 - (union of device-operation intervals / traced window), on the chip
that was busy least."""

LAYER = "device"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import tracered

    if trace is None or not trace.device_ops:
        return None
    window = trace.window[1] - trace.window[0]
    if window <= 0:
        return None
    return 100.0 * (1.0 - min(tracered.busy_seconds(trace).values()) / window)
