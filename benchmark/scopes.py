#!/usr/bin/env python3
"""From a device trace to the seconds each phase of the train step took.

The program names three scopes (``dpwa_tpu/utils/scopes.py``): the loss call
under ``dpwa.forward`` inside ``jax.value_and_grad``, the optimizer's
arithmetic under ``dpwa.optimizer``, both exchange bodies under
``dpwa.exchange``.  JAX writes a scope into the ``op_name`` of every HLO
instruction traced under it and wraps it when it differentiates, so one name
gives two phases: ``.../vmap(jvp(dpwa.forward))/dot_general`` is forward and
``.../vmap(transpose(jvp(dpwa.forward)))/dot_general`` backward (without the
``vmap(`` under ``shard_map``; a rematerialised forward lands under
``transpose(``, in backward, where its time is spent).

Where the scope sits in a v5e trace (looked at by hand; my chip run, PR 24):
an event of a chip's ``XLA Ops`` line has three stats of its own
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``) and
its text ends before any ``metadata={...}``.  The ``op_name`` is the stat
**``tf_op``** of the event's *metadata* (the plane's ``event_metadata`` map,
one entry an instruction, beside ``hlo_category``, ``flops``,
``bytes_accessed``, ``source`` and ``source_stack``), written as
``jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_0/mlp/w_gate/dot_general:``
(the name, a colon, and an empty type).  ``jax.profiler.ProfileData`` shows
an event's own stats only, so the metadata is read here from the file's
protobuf wire format (``xplane.proto``: about thirty lines, no package
needed); times still come from ``ProfileData``, as in ``tracered``.

What a reader of these numbers must know:

- the compiler fuses across scopes, and an event has one name: a fusion is
  booked whole to the scope of its own ``op_name`` (PERF.md counts the mixed
  fusions of each cell from the compiled text);
- the copies and slices the compiler adds (``copy-start`` / ``-done``,
  ``slice-start`` / ``-done``) carry no ``op_name``: they, the clock and the
  step counter are the fifth number, ``unscoped``;
- time is *self* time (a ``conditional`` does not count the
  ``collective-permute`` inside it twice), inside the window of the
  ``bench.*`` spans, and the chip reported is the one where the five sum
  highest: the chip that sets the pace.

    python benchmark/scopes.py <file.xplane.pb>

prints the stats of the first few ``XLA Ops`` events and the table of phases;

    JAX_PLATFORMS=cpu python benchmark/scopes.py --mixed <workload> [<file>]

compiles the cell's step for a described v5e (no chip) and counts the fusions
that mix phases, with their share of the device time of a traced run.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import tracered  # noqa: E402

PHASES = ("forward", "backward", "optimizer", "exchange", "unscoped")
# The stat of an event's metadata that holds the instruction's op_name.
SCOPE_STAT = "tf_op"
# Where run.py writes a traced run's files; a reader is handed the reduced
# trace and no path, and finds the file again here by its window.
TRACE_ROOT = os.path.join(HERE, "out", "trace")


def phase_of(op_name: str):
    """The phase an ``op_name`` lies under, or None under no scope.  Where
    the compiler joined several names with ``;``, the first is read."""
    op_name = op_name.partition(";")[0]
    if "dpwa.exchange" in op_name:
        return "exchange"
    if "dpwa.optimizer" in op_name:
        return "optimizer"
    for part in op_name.split("/"):
        if "dpwa.forward" in part:
            # transpose(jvp(dpwa.forward)), and vmap(transpose(vmap(jvp(...
            return "backward" if "transpose(" in part else "forward"
    return None


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def op_names(path: str) -> dict:
    """Chip -> {instruction text: op_name}, from the ``tf_op`` stat of each
    event metadata of the chip's plane.  Field numbers are xplane.proto's:
    XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5 (maps:
    value 2); XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7."""
    text = lambda view: bytes(view).decode("utf-8", "replace")
    with open(path, "rb") as f:
        space = memoryview(f.read())
    chips = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for number, value in _fields(plane):
            if number == 2:
                name = text(value)
            elif number in (4, 5):
                entry = next((v for n, v in _fields(value) if n == 2), None)
                if entry is None:
                    continue
                if number == 4:
                    metadata.append(entry)
                else:
                    stat = dict(_fields(entry))
                    stat_names[stat.get(1, 0)] = text(stat.get(2, b""))
        match = tracered.DEVICE_PLANE.match(name)
        if not match:
            continue
        names = chips.setdefault(int(match.group(1)), {})
        for entry in metadata:
            instruction, op_name = "", ""
            for number, value in _fields(entry):
                if number == 2:
                    instruction = text(value)
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        op_name = text(stat[5])
                    elif 7 in stat:  # a string kept once, among the stat names
                        op_name = stat_names.get(stat[7], "")
            if op_name:
                names[instruction] = op_name.rstrip(":")
    return chips


def self_times_in(ops, window) -> list:
    """[(event, self seconds)] of one chip's events (sorted by start, longest
    first) clipped to ``window``."""
    lo, hi = window
    return tracered.self_times([
        e._replace(start=max(e.start, lo), end=min(e.end, hi))
        for e in ops if e.end > lo and e.start < hi
    ])


def book(ops, window) -> dict:
    """{phase: self seconds} of one chip's events (``detail`` = op_name)
    inside ``window``."""
    seconds = dict.fromkeys(PHASES, 0.0)
    for event, own in self_times_in(ops, window):
        seconds[phase_of(event.detail) or "unscoped"] += own
    return seconds


def scoped_ops(path: str) -> dict:
    """Chip -> [Event] of its ``XLA Ops`` line, sorted by start and longest
    first, with the op_name as ``detail`` (empty where the instruction has
    none).  A CPU trace has no device plane: {}."""
    import jax

    names = op_names(path)
    data = jax.profiler.ProfileData.from_file(path)
    chips = {}
    for plane in data.planes:
        match = tracered.DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        chip = int(match.group(1))
        of = names.get(chip, {})
        for line in plane.lines:
            if line.name != tracered.OPS_LINE:
                continue
            chips.setdefault(chip, []).extend(
                tracered.Event(
                    e.name.partition(" = ")[0].lstrip("%"),
                    e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    of.get(e.name, ""),
                )
                for e in line.events
            )
    for ops in chips.values():
        ops.sort(key=lambda e: (e.start, -e.end))
    return chips


def phase_seconds(path: str, trace=None) -> dict:
    """{chip: {phase: seconds}} inside the window of the ``bench.*`` spans
    (``trace`` is ``tracered.load(path)``, where the caller has it)."""
    window = (trace or tracered.load(path)).window
    return {dev: book(ops, window) for dev, ops in scoped_ops(path).items()}


def pace_setter(per_chip: dict):
    """The chip whose phases sum highest, and its {phase: seconds}; (None,
    None) where no event lies under a scope: a program without scopes has
    no phases, and none is reported."""
    if not any(
        seconds for phases in per_chip.values()
        for phase, seconds in phases.items() if phase != "unscoped"
    ):
        return None, None
    chip = max(per_chip, key=lambda dev: sum(per_chip[dev].values()))
    return chip, per_chip[chip]


@functools.lru_cache(maxsize=2)
def _of_window(window, root):
    """The phases of the traced run whose file under ``root`` reduces to
    exactly ``window`` (the same file gives the same floats), newest first;
    None when none does."""
    found = [
        os.path.join(d, f) for d, _, files in os.walk(root)
        for f in files if f.endswith(".xplane.pb")
    ]
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        trace = tracered.load(path)
        if trace.window == window:
            return pace_setter(phase_seconds(path, trace))[1]
    return None


def phase_ms_per_step(trace, record, phase: str):
    """What each ``benchmark/layer_metrics/<phase>_ms_per_step.py`` returns:
    ms of ``phase`` a traced step on the chip that sets the pace; None where
    there is no trace, no traced step, no file to read the scopes from or no
    scope in it."""
    if trace is None or not record["traced_steps"] or not trace.device_ops:
        return None
    seconds = _of_window(tuple(trace.window), TRACE_ROOT)
    if seconds is None:
        return None
    return 1e3 * seconds[phase] / record["traced_steps"]


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = .*? ([a-z][\w-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def mixed_fusions(compiled_text: str) -> list:
    """[(fusion's name, the phase its own op_name books it to, the sorted
    phases among the instructions it fused)] for every fusion of
    ``compiled.as_text()`` that runs as an instruction of its own (not
    inside another fusion) and fused instructions of two or more phases.
    A trace event has one name, so such a fusion's time is booked whole to
    the first of these: this is how far to trust the split."""
    inside, nested, fusions, current = {}, {}, [], None
    for line in compiled_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(1)
            inside[current], nested[current] = set(), []
            continue
        found = _INSTRUCTION.match(line)
        if not found or current is None:
            continue
        name = _OP_NAME.search(line)
        phase = phase_of(name.group(1)) if name else None
        if phase:
            inside[current].add(phase)
        calls = _CALLS.search(line)
        if found.group(2) == "fusion" and calls:
            nested[current].append(calls.group(1))
            fusions.append(
                (found.group(1), phase or "unscoped", calls.group(1), current)
            )
    fused = {called for _, _, called, _ in fusions}

    def phases(computation):
        found = set(inside.get(computation, ()))
        for called in nested.get(computation, ()):
            found |= phases(called)
        return found

    mixed = [
        (name, booked, sorted(phases(called)))
        for name, booked, called, within in fusions if within not in fused
    ]
    return [entry for entry in mixed if len(entry[2]) > 1]


def compiled_step_text(workload: str) -> str:
    """The cell's real step compiled for a described v5e, as text.  The
    compile is ``benchmark/rehearse_compile.py``'s, whole; the text is caught
    on its way into that script's report."""
    from unittest import mock

    import jax

    from benchmark import rehearse_compile

    texts, as_text = [], jax.stages.Compiled.as_text

    def caught(compiled, *args, **kwargs):
        texts.append(as_text(compiled, *args, **kwargs))
        return texts[-1]

    with mock.patch.object(jax.stages.Compiled, "as_text", caught):
        rehearse_compile.main([workload, "--no-reference"])
    return texts[0]


def describe_mixed(workload: str, path=None) -> None:
    """Print the cell's mixed fusions by the phases they mix and, given the
    trace of a run of the same step, the share of the step's device time
    each kind is.  Events and instructions are matched by name; the share of
    the trace's fusion events that the text knows says whether the two are
    the same program."""
    text = compiled_step_text(workload)
    kinds = {
        name: f"{'+'.join(phases)} booked as {booked}"
        for name, booked, phases in mixed_fusions(text)
    }
    count = {}
    for kind in kinds.values():
        count[kind] = count.get(kind, 0) + 1
    print("MIXED", workload, len(kinds), json.dumps(count, sort_keys=True))
    if path is None:
        return
    known = {m.group(1) for m in map(_INSTRUCTION.match, text.splitlines()) if m}
    trace = tracered.load(path)
    chips = scoped_ops(path)
    chip, _ = pace_setter({d: book(o, trace.window) for d, o in chips.items()})
    total, seconds, fusions, matched = 0.0, {}, set(), set()
    for event, own in self_times_in(chips[chip], trace.window):
        total += own
        if "fusion" in event.name:
            fusions.add(event.name)
            if event.name in known:
                matched.add(event.name)
        if event.name in kinds:
            kind = kinds[event.name]
            seconds[kind] = seconds.get(kind, 0.0) + own
    print("MIXED_SHARE", workload, "chip", chip,
          f"{100 * sum(seconds.values()) / total:.2f} % of {total:.6f} s;",
          f"{len(matched)} of the trace's {len(fusions)} fusions are in the text;",
          json.dumps({k: round(100 * v / total, 3) for k, v in sorted(seconds.items())}))


def describe(path: str, events: int = 4, largest: int = 5) -> None:
    """Print what the first few ``XLA Ops`` events carry (their own stats,
    and the op_name found for them), then the table of phases with each
    phase's largest folded instruction names."""
    import jax

    names = op_names(path)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        match = tracered.DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        of = names.get(int(match.group(1)), {})
        print("PLANE", plane.name, "instructions with an op_name:", len(of))
        for line in plane.lines:
            if line.name != tracered.OPS_LINE:
                continue
            shown = 0
            for e in line.events:
                # The first events are a step's small prologue: show a few
                # of them, and a few that lie under a scope.
                scoped = "dpwa." in of.get(e.name, "")
                if shown < events or (scoped and shown < 2 * events):
                    shown += 1
                    print("EVENT", e.name[:160])
                    print("   STATS", [(k, str(v)[:60]) for k, v in e.stats])
                    print("   OP_NAME", of.get(e.name, ""))
                if shown >= 2 * events:
                    break
        break  # one chip is enough to see where the scope sits
    trace = tracered.load(path)
    chips = scoped_ops(path)
    per_chip = {dev: book(ops, trace.window) for dev, ops in chips.items()}
    print("WINDOW", trace.window, "BUSY", tracered.busy_seconds(trace),
          "BOOKED", {d: sum(s.values()) for d, s in per_chip.items()})
    chip, seconds = pace_setter(per_chip)
    if chip is None:
        print("no event lies under a dpwa.* scope")
        return
    by_phase = {phase: {} for phase in PHASES}
    for event, own in self_times_in(chips[chip], trace.window):
        folded = by_phase[phase_of(event.detail) or "unscoped"]
        key = tracered.fold(event.name)
        folded[key] = folded.get(key, 0.0) + own
    total = sum(seconds.values())
    for phase in PHASES:
        print(f"PHASE chip {chip} {phase:9s} {seconds[phase]:.6f} s "
              f"{100 * seconds[phase] / total:5.1f} %")
        ranked = sorted(by_phase[phase].items(), key=lambda kv: -kv[1])
        for name, own in ranked[:largest]:
            print(f"      {own:.6f} {name}")


if __name__ == "__main__":
    if sys.argv[1] == "--mixed":  # --mixed <workload> [<file.xplane.pb>]
        describe_mixed(*sys.argv[2:4])
    else:
        describe(sys.argv[1])
