"""Device self time of the two halves of a sparse-expert layer, from the
``dpwa.moe.route`` and ``dpwa.moe.experts`` scopes of ``ops/moe.py``
(``dpwa_tpu/utils/scopes.py``).  Both nest under ``dpwa.forward``, so their
time is part of ``forward_ms_per_step`` + ``backward_ms_per_step``; here it
is summed forward and backward together, on the chip that sets the pace, by
the machinery of ``benchmark/scopes.py`` (events with their ``op_name``, self
times, the window of the ``bench.*`` spans)."""

from __future__ import annotations

import functools
import os

from benchmark import scopes, tracered

SCOPES = {"route": "dpwa.moe.route", "experts": "dpwa.moe.experts"}


def book(ops, window) -> dict:
    """{"route" | "experts": self seconds} of one chip's events."""
    seconds = dict.fromkeys(SCOPES, 0.0)
    for event, own in scopes.self_times_in(ops, window):
        op_name = event.detail.partition(";")[0]
        for key, scope in SCOPES.items():
            if scope in op_name:
                seconds[key] += own
                break
    return seconds


def seconds_in(path: str, trace=None):
    """:func:`book` of the chip whose phases sum highest in the trace at
    ``path``; None where no event lies under either scope (a program without
    an expert layer, or without the scopes)."""
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


def scope_seconds_per_step(trace, record, key: str):
    """Seconds of ``SCOPES[key]`` a traced step, or None where there is no
    trace, no traced step, no file, or nothing under the scope."""
    if trace is None or not record["traced_steps"] or not trace.device_ops:
        return None
    seconds = _of_window(tuple(trace.window), scopes.TRACE_ROOT)
    if seconds is None:
        return None
    return seconds[key] / record["traced_steps"]
