"""Device self time of one step in the instructions that ``jax.checkpoint``
recomputes (``rematted_computation`` in their ``op_name``): a block's forward
run a second time in the backward pass, which the phases book under
``backward_ms_per_step`` and ``mfu`` does not count as required work
(``benchmark/latent_scopes.py``)."""

LAYER = "models"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes

    seconds = latent_scopes.group_seconds_per_step(trace, record, "recompute")
    return None if seconds is None else 1e3 * seconds
