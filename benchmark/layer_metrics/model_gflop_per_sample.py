"""The benchmark's own count of the FLOPs forward and backward require."""

LAYER = "models"
UNIT = "GFLOP"
MOVES = "mfu"
SOURCE = "program_counter"


def reduce(trace, record):
    return record["flops_per_sample"] / 1e9
