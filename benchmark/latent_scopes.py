"""Device self time under the names that the latent-attention family adds to
a step's ``op_name``s: the ``dpwa.attn.latent`` and ``dpwa.moe.shared``
scopes of ``models/llama.py`` (``dpwa_tpu/utils/scopes.py``), and
``rematted_computation``, which JAX writes into the name of every
instruction that ``jax.checkpoint`` recomputes in the backward pass.  (The
two scopes of the routed experts are ``benchmark/moe_scopes.py``'s: its
readers' lists are held to one cell by an accepted test, so the expert layer
whole, routed and shared, is a group here.)  All nest under ``dpwa.forward``; a group's time is summed forward,
backward and recomputed together, on the chip that sets the pace, by the
machinery of ``benchmark/scopes.py``.  The groups overlap (a recomputed
attention instruction counts under ``latent_attn`` and under ``recompute``),
so they do not sum to anything."""

from __future__ import annotations

import functools
import os

from benchmark import scopes, tracered

# group -> the names of which one must be part of an instruction's op_name.
GROUPS = {
    "latent_attn": ("dpwa.attn.latent",),
    "expert_share": ("dpwa.moe.route", "dpwa.moe.experts", "dpwa.moe.shared"),
    "recompute": ("rematted_computation",),
}


def book(ops, window) -> dict:
    """{group: self seconds} of one chip's events."""
    seconds = dict.fromkeys(GROUPS, 0.0)
    for event, own in scopes.self_times_in(ops, window):
        op_name = event.detail.partition(";")[0]
        for group, names in GROUPS.items():
            if any(name in op_name for name in names):
                seconds[group] += own
    return seconds


def seconds_in(path: str, trace=None):
    """:func:`book` of the chip whose phases sum highest in the trace at
    ``path``; None where no event lies under any of the names (a program
    without latent attention, or without the scopes)."""
    window = (trace or tracered.load(path)).window
    chips = scopes.scoped_ops(path)
    chip, _ = scopes.pace_setter(
        {dev: scopes.book(ops, window) for dev, ops in chips.items()}
    )
    if chip is None:
        return None
    seconds = book(chips[chip], window)
    return seconds if any(seconds.values()) else None


@functools.lru_cache(maxsize=2)
def _of_window(window, root):
    """As ``scopes._of_window``: a reader is handed the reduced trace and no
    path, so the file is found again under ``root`` by its window."""
    found = [
        os.path.join(d, f) for d, _, files in os.walk(root)
        for f in files if f.endswith(".xplane.pb")
    ]
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        trace = tracered.load(path)
        if tuple(trace.window) == window:
            return seconds_in(path, trace)
    return None


def group_seconds_per_step(trace, record, group: str):
    """Seconds of ``GROUPS[group]`` a traced step, or None where there is no
    trace, no traced step, no file, or nothing under the group's names."""
    if trace is None or not record["traced_steps"] or not trace.device_ops:
        return None
    seconds = _of_window(tuple(trace.window), scopes.TRACE_ROOT)
    if not seconds or not seconds[group]:
        return None
    return seconds[group] / record["traced_steps"]


def roofline_share(record, work_key: str, seconds):
    """100 x the least time the chip could take for ``kernel_work[work_key]``
    a step (the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s) over
    ``seconds`` a step; None where either is missing."""
    from benchmark import flops

    work = (record.get("kernel_work") or {}).get(work_key)
    if not work or not seconds:
        return None
    peak = flops.peak(record["device_kind"])
    floor = max(
        work["flops"] / peak["bf16_flops_per_s"],
        work["bytes"] / peak["hbm_bytes_per_s"],
    )
    return 100.0 * floor / seconds
