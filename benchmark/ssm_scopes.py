"""Device self time under the names that the state-space family adds to a
step's ``op_name``s: ``dpwa.ssm`` around a whole Mamba mixer
(``models/llama.MambaMixer``) and, inside it, ``dpwa.ssm.scan`` around the
selective scan alone (``ops/ssm.py``; ``dpwa_tpu/utils/scopes.py`` has both).
They nest under ``dpwa.forward``; a group's time is summed forward, backward
and recomputed together, on the chip that sets the pace, by the machinery of
``benchmark/scopes.py``, as ``benchmark/latent_scopes.py`` does for its
names.  The scan's time is part of the mixer's."""

from __future__ import annotations

import functools
import os

from benchmark import scopes, tracered

# group -> the name that must be part of an instruction's op_name.
GROUPS = {"ssm_mixer": "dpwa.ssm", "ssm_scan": "dpwa.ssm.scan"}


def book(ops, window) -> dict:
    """{group: self seconds} of one chip's events."""
    seconds = dict.fromkeys(GROUPS, 0.0)
    for event, own in scopes.self_times_in(ops, window):
        op_name = event.detail.partition(";")[0]
        for group, name in GROUPS.items():
            if name in op_name:
                seconds[group] += own
    return seconds


def seconds_in(path: str, trace=None):
    """:func:`book` of the chip whose phases sum highest in the trace at
    ``path``; None where no event lies under either name (a program without
    a state-space mixer, or without the scopes)."""
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
    trace, no traced step, no file, or nothing under the group's name."""
    if trace is None or not record["traced_steps"] or not trace.device_ops:
        return None
    seconds = _of_window(tuple(trace.window), scopes.TRACE_ROOT)
    if not seconds or not seconds[group]:
        return None
    return seconds[group] / record["traced_steps"]
