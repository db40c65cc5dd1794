"""Device self time of one step under ``dpwa.attn.latent``: latent
attention's down and up projections with their norms and adapters, rope, the
attention core and the output projection, forward, backward and recomputed
together, on the chip that sets the pace (``benchmark/latent_scopes.py``)."""

LAYER = "latent attention"
UNIT = "ms"
MOVES = "samples_per_s"
SOURCE = "device_trace"


def reduce(trace, record):
    from benchmark import latent_scopes

    seconds = latent_scopes.group_seconds_per_step(trace, record, "latent_attn")
    return None if seconds is None else 1e3 * seconds
