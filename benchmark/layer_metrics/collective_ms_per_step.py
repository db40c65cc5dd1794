"""Time in flight of the ``collective-permute`` operations of one step, on
the chip where it is longest: from the start of ``-start`` to the end of
``-done`` (or the synchronous op's own event)."""

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
    worst = max(sum(e - s for s, e in iv) for _, iv in chips)
    return 1e3 * worst / record["traced_steps"]
