"""Bytes one peer ships in one exchange, from the exchanged leaves' shapes
and the wire dtype (benchmark/flops.py)."""

LAYER = "exchange"
UNIT = "MB"
MOVES = "samples_per_s"
SOURCE = "program_counter"


def reduce(trace, record):
    from benchmark import flops

    return flops.exchange_bytes_per_peer(
        record["leaf_sizes"], record["cell"]["wire_dtype"]
    ) / 1e6
