"""The short convolutions' gates' share of their roofline: the least time the
chip could take for what a step's gates must move and do
(``benchmark/flops_lfm2.conv_gate_required``, handed over as
``kernel_work["conv_gate"]``: ``b c u`` read and ``c * z`` written once
forward, those and the gradient read and three gradients written once
backward; the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s; the
bytes bound) over the time under ``dpwa.conv.gate`` the trace shows
(``benchmark/block_scopes.ms_per_step`` over this table).  What XLA fuses
into a neighbouring matmul leaves the scope's time and raises the share: the
share says how far the gate is from costing only its memory traffic, whatever
implements it."""

LAYER = "short convolution"
UNIT = "%"
MOVES = "samples_per_s"
SOURCE = "device_trace"
GROUPS = {"conv_gate": ("dpwa.conv.gate",)}


def reduce(trace, record):
    from benchmark import block_scopes, latent_scopes

    ms = block_scopes.ms_per_step(trace, record, "conv_gate", GROUPS)
    return latent_scopes.roofline_share(
        record, "conv_gate", None if ms is None else 1e-3 * ms
    )
