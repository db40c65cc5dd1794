"""Block time / block_steps, median over the untraced blocks of the window."""

LAYER = "step builders"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "host_clock"


def reduce(trace, record):
    import statistics

    if not record["blocks"]:
        return None
    return 1e3 * statistics.median(record["blocks"]) / record["block_steps"]
