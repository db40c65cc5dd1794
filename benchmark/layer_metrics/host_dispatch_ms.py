"""Host clock inside ``bench.step_call``: the time until the step call
returns, median over the window's calls.  Where it nears the step time, the
host sets the pace."""

LAYER = "step builders"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "host_clock"


def reduce(trace, record):
    import statistics

    if not record["dispatch_ms"]:
        return None
    return statistics.median(record["dispatch_ms"])
