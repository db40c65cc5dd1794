"""Host clock around the builder, the transport, init and the state build,
the batch pool and the step builder (everything before the first step call)."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def reduce(trace, record):
    return record["state_setup_s"]
