"""The part of ``collective_ms_per_step`` during which no other operation
ran on that chip: what the exchange adds to the step."""

LAYER = "exchange"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import tracered

    if trace is None or not record["traced_steps"]:
        return None
    chips = tracered.collectives_per_chip(trace)
    if not chips:
        return None
    worst = max(tracered.exposed_seconds(ops, iv) for ops, iv in chips)
    return 1e3 * worst / record["traced_steps"]
