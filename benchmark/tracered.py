"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a trace of this
program looks like on a v5e (looked at by hand, PERF.md section 3):

- a plane per chip named ``/device:TPU:<i>``; its line ``XLA Ops`` holds one
  event per executed HLO instruction, and the event's name is the
  instruction's whole text (``%fusion.123 = bf16[...] fusion(...)``): the
  part before `` = `` is the instruction's name, the rest is kept as
  ``detail``.  Control flow (``while``, ``conditional``) is an event that
  *encloses* the events of its body.  The line ``Async XLA Ops`` holds what
  runs beside the ops (``copy-start`` to ``copy-done``, slices, collectives in
  flight) and is read only for collectives.  ``XLA Modules`` repeats the same
  time a program at a time and is not read;
- the library flash-attention kernels are ``flash_attention.N`` (forward),
  ``flash_mha_bwd_dkv_<blocks>.N`` and ``flash_mha_bwd_dq_<blocks>.N``;
- the plane ``/host:CPU`` holds a line per host thread; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench.*``) are events of the line
  ``python``, on the same clock.

A trace from the CPU backend has no device plane; there the ops sit on the
``tf_XLA*`` thread lines of ``/host:CPU`` and carry an ``hlo_op`` stat.  They
are read as one device so that the reduction can be rehearsed and tested
without a chip; nothing from such a trace is ever reported as a device metric.
"""

from __future__ import annotations

import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+)+$")


class Event(NamedTuple):
    name: str  # the instruction's name, or the span's
    start: float  # seconds on the trace's clock
    end: float
    detail: str  # the rest of the instruction's text, where the trace has it


class Trace(NamedTuple):
    device_ops: dict  # device index -> [Event], sorted by start
    host_spans: list  # [Event] named bench.*, sorted by start
    window: tuple  # (start, end) seconds: first to last bench span
    async_ops: dict = {}  # device index -> [Event] of the async line


def fold(name: str) -> str:
    """An instruction's name without ``%`` and its ``.N`` suffixes."""
    return _SUFFIX.sub("", name.lstrip("%")) or name


def _op(event) -> Event:
    name, _, detail = event.name.partition(" = ")
    return Event(
        name.lstrip("%"), event.start_ns * 1e-9,
        (event.start_ns + event.duration_ns) * 1e-9, detail[:300],
    )


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_ops, async_ops, spans = {}, {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                into = {OPS_LINE: device_ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is not None:
                    into.setdefault(int(match.group(1)), []).extend(
                        _op(e) for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(_op(e))
                    elif line.name.startswith("tf_XLA") and e.duration_ns and any(
                        key == "hlo_op" for key, _ in e.stats
                    ):  # CPU backend: an HLO op on a worker thread
                        device_ops.setdefault("cpu", []).append(_op(e))
    if "cpu" in device_ops and len(device_ops) > 1:
        del device_ops["cpu"]
    for ops in list(device_ops.values()) + list(async_ops.values()):
        ops.sort(key=lambda e: (e.start, -e.end))
    spans.sort(key=lambda e: e.start)
    if spans:
        window = (spans[0].start, max(s.end for s in spans))
    else:
        starts = [o[0].start for o in device_ops.values() if o]
        ends = [max(e.end for e in o) for o in device_ops.values() if o]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Trace(device_ops, spans, window, async_ops)


def union(intervals, lo=float("-inf"), hi=float("inf")) -> list:
    """Merged, sorted intervals clipped to [lo, hi]."""
    merged = []
    for start, end in sorted(
        (max(s, lo), min(e, hi)) for s, e in intervals
    ):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_seconds(trace: Trace) -> dict:
    """Device index -> seconds inside the window in which an op ran."""
    lo, hi = trace.window
    return {
        dev: _length(union(((e.start, e.end) for e in ops), lo, hi))
        for dev, ops in trace.device_ops.items()
    }


def self_times(ops) -> list:
    """[(event, self seconds)]: an event's time less what the events nested
    inside it cover, so that a ``while`` or ``conditional`` does not count its
    body twice.  ``ops`` is sorted by (start, -end)."""
    out, stack = [], []  # stack of [event, covered seconds]

    def close(until):
        while stack and stack[-1][0].end <= until:
            event, covered = stack.pop()
            out.append((event, max(0.0, event.end - event.start - covered)))
            if stack:
                stack[-1][1] += event.end - event.start

    for event in ops:
        close(event.start)
        stack.append([event, 0.0])
    close(float("inf"))
    return out


def top_ops(trace: Trace, count: int = 10) -> list:
    """[[folded name, self seconds a chip]], largest first, inside the
    window, averaged over the chips."""
    lo, hi = trace.window
    totals = {}
    for ops in trace.device_ops.values():
        for event, seconds in self_times(
            [e for e in ops if e.end > lo and e.start < hi]
        ):
            name = fold(event.name)
            totals[name] = totals.get(name, 0.0) + seconds
    chips = max(1, len(trace.device_ops))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:count]
    return [[name, seconds / chips] for name, seconds in ranked]


def idle_gaps(trace: Trace, count: int = 5) -> list:
    """The longest idle gaps of the busiest-gapped chip, grouped by the
    benchmark span that covered most of each: [[span name, seconds]] summed
    by name, with the ``count`` largest sums.  A gap under no span is
    ``outside_spans``."""
    lo, hi = trace.window
    if not trace.device_ops or hi <= lo:
        return []
    worst = min(busy_seconds(trace).items(), key=lambda kv: kv[1])[0]
    busy = union(
        ((e.start, e.end) for e in trace.device_ops[worst]), lo, hi
    )
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    by_span = {}
    for start, end in gaps:
        if end <= start:
            continue
        best, cover = "outside_spans", 0.0
        for span in trace.host_spans:
            if span.start >= end:
                break
            overlap = min(end, span.end) - max(start, span.start)
            if overlap > cover:
                best, cover = span.name, overlap
        by_span[best] = by_span.get(best, 0.0) + (end - start)
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:count]
    return [[name, seconds] for name, seconds in ranked]


def matching(trace: Trace, pattern: str) -> dict:
    """Device index -> [Event] whose instruction name matches ``pattern``,
    inside the window."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return {
        dev: [
            e for e in ops
            if e.end > lo and e.start < hi and rx.search(e.name)
        ]
        for dev, ops in trace.device_ops.items()
    }


def collective_intervals(ops, stem: str = "collective-permute") -> list:
    """[(start, end)] of each collective on one chip: a synchronous
    ``<stem>.N`` is its own event; an asynchronous one runs from the start
    of ``<stem>-start.N`` to the end of the next ``<stem>-done`` after it."""
    out, pending = [], []
    for e in ops:
        name = fold(e.name)
        if name == stem:
            out.append((e.start, e.end))
        elif name == f"{stem}-start":
            pending.append(e.start)
        elif name == f"{stem}-done" and pending:
            out.append((pending.pop(0), e.end))
    return out


def exposed_seconds(ops, intervals, stem: str = "collective-permute") -> float:
    """The part of ``intervals`` during which no op other than the
    collective's own (and the control flow that encloses it) ran."""
    total = 0.0
    for lo, hi in intervals:
        others = union(
            (
                (e.start, e.end) for e in ops
                if not fold(e.name).startswith(stem)
                and not (e.start <= lo and e.end >= hi)
            ),
            lo, hi,
        )
        total += (hi - lo) - _length(others)
    return total


# How the library flash-attention kernels (forward, dq, dkv) are named in a
# v5e trace (the head of this file).
FLASH_KERNEL = r"^(flash_attention|flash_mha_bwd)"


def kernel_seconds(trace: Trace, pattern: str):
    """Summed device seconds of the events matching ``pattern``, on the chip
    where that is largest; None where no event matches."""
    per_chip = [
        sum(e.end - e.start for e in events)
        for events in matching(trace, pattern).values() if events
    ]
    return max(per_chip) if per_chip else None


def collectives_per_chip(trace: Trace) -> list:
    """[(the chip's ops inside the window, its collective intervals)] for
    each chip that ran a collective-permute."""
    lo, hi = trace.window
    out = []
    for dev, ops in trace.device_ops.items():
        ops = [e for e in ops if e.end > lo and e.start < hi]
        in_flight = [
            (e.start, e.end) for e in trace.async_ops.get(dev, ())
            if e.end > lo and e.start < hi
            and fold(e.name).startswith("collective-permute")
        ]
        intervals = union(collective_intervals(ops) + in_flight, lo, hi)
        if intervals:
            out.append((ops, [tuple(iv) for iv in intervals]))
    return out


def describe(path: str, count: int = 40) -> None:
    """Print what a trace holds: planes, lines, and the op names with most
    time.  For looking at a trace by hand before writing a reader."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print("PLANE", plane.name, lines[:12])
    trace = load(path)
    print("WINDOW", trace.window, "SPANS", len(trace.host_spans))
    print("BUSY", busy_seconds(trace))
    for name, seconds in top_ops(trace, count):
        print(f"OP {seconds:.6f} {name}")
    seen = set()
    for ops in list(trace.device_ops.values()) + list(trace.async_ops.values()):
        for e in ops:
            key = fold(e.name)
            if key not in seen and ("custom-call" in e.detail or "collective" in key):
                seen.add(key)
                print("NAMED", e.name, "|", e.detail[:200])
    print("GAPS", idle_gaps(trace))
    print("FLASH", kernel_seconds(trace, FLASH_KERNEL))
    print("COLLECTIVES", [
        (len(iv), sum(e - s for s, e in iv), exposed_seconds(ops, iv))
        for ops, iv in collectives_per_chip(trace)
    ])


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
