"""Host clock around the first step call less one steady step: the step
program's compile, or its load from the persistent cache."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def reduce(trace, record):
    return record["compile_s"]
