"""FLOPs and bytes that a decoder with EVA attention and several next-byte
heads *requires* under LoRA fine-tuning, from shapes alone (``family:
eva_decoder``; the conventions of ``benchmark/flops.py`` hold: a multiply-add
is two operations, no base-weight gradient, no optimizer, no exchange, no
recomputed block, plain Python on numbers).

A query of window ``w`` (``window_size`` positions a window) has as keys the
positions of its own window up to itself and ``window_size / chunk_size``
summaries of each of the ``w`` windows before it."""

from __future__ import annotations

from benchmark.flops_latent import (
    _adapter_values, _values, swiglu_projections,
)

# The core's backward pass over its forward: dV, dP, dQ and dK, and the
# scores once more, because no pass keeps them (five matmuls to two).
CORE_BACKWARD = 2.5
# Operations a (token, head, dim) of the summaries, forward: k . phi, the
# pooled key and the pooled value, a multiply-add each.
SUMMARY_FORWARD_OPS = 6


def attention_projections(config: dict) -> dict:
    d = config["hidden_size"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d)}


def parts(config: dict, rank: int) -> dict:
    """``(frozen, adapter)`` values that multiply one token's activations:
    a layer's ``attention`` projections, its ``mlp``, and the ``head`` of
    ``vocab_size x num_pred_heads`` columns."""
    one = lambda shapes: (_values(shapes), _adapter_values(shapes, rank))
    d = config["hidden_size"]
    return dict(
        attention=one(attention_projections(config)),
        mlp=one(swiglu_projections(d, config["intermediate_size"])),
        head=(d * config["vocab_size"] * config["num_pred_heads"], 0),
    )


def score_entries(config: dict, seq_len: int) -> dict:
    """(query, key) pairs a head of one sequence must score: ``local``, the
    windows' lower triangles with their diagonals, and ``remote``, every
    query of window ``w`` against the summaries of the ``w`` windows before
    it.  ``seq_len`` is a whole number of windows."""
    window, chunk = config["window_size"], config["chunk_size"]
    windows, rest = divmod(seq_len, window)
    if rest or window % chunk:
        raise ValueError(
            f"{seq_len} positions are no whole number of windows of {window}"
            f" in chunks of {chunk}"
        )
    return dict(
        local=windows * window * (window + 1) // 2,
        remote=window * (window // chunk) * windows * (windows - 1) // 2,
    )


def core_forward_flops(config: dict, seq_len: int) -> float:
    """QK^T and PV over :func:`score_entries`, every head of one layer of one
    sequence."""
    entries = score_entries(config, seq_len)
    d = config["hidden_size"]  # heads x head size
    return 2 * 2 * d * float(entries["local"] + entries["remote"])


def eva_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """Base matmuls forward and backward to the activations, adapters
    forward, backward and their own gradients, the core as
    :func:`eva_core_required` counts it, the summaries' elementwise work
    (forward and twice that backward), the head over all its columns."""
    p = parts(config, rank)
    layers = config["num_hidden_layers"]
    frozen = layers * (p["attention"][0] + p["mlp"][0]) + p["head"][0]
    adapters = layers * (p["attention"][1] + p["mlp"][1])
    core = (1 + CORE_BACKWARD) * core_forward_flops(config, seq_len) / seq_len
    pooled = 3 * SUMMARY_FORWARD_OPS * config["hidden_size"]
    return float(
        2 * 2 * frozen + 3 * 2 * adapters + layers * (core + pooled)
    )


def eva_core_required(
    config: dict, seq_len: int, sequences: int, dtype_bytes: int = 2
) -> dict:
    """What the core's kernels of one training step must do over
    ``sequences`` sequences, whatever implements them.  FLOPs: the forward's
    two matmuls over :func:`score_entries` once and :data:`CORE_BACKWARD`
    times that backward; a recomputed block's second forward is not counted.
    HBM bytes: the forward reads ``q k v ksum vsum`` and writes ``o``; the
    backward reads those, ``o`` and ``do`` and writes ``dq dk dv dksum
    dvsum``, once each (the log-sum-exp rows are a 256th of a tensor)."""
    layers = config["num_hidden_layers"]
    tensor = seq_len * config["hidden_size"] * dtype_bytes
    summary = tensor // config["chunk_size"]
    return dict(
        flops=(1 + CORE_BACKWARD) * core_forward_flops(config, seq_len)
        * layers * sequences,
        bytes=float((12 * tensor + 6 * summary) * layers * sequences),
    )
