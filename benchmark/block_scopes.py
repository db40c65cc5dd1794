#!/usr/bin/env python3
"""What lies under ``dpwa.forward``, by the part of the decoder it belongs to.

``dpwa_tpu/utils/scopes.py`` names the parts of a decoder inside the forward
scope: plain and latent attention, the dense feed-forward, the expert layer's
three parts, the state-space mixer with its scan, the head and the loss.
:data:`GROUPS` is the one table of them, and :func:`book` the one booking
function over *a table handed in*: device self time on the chip that sets the
pace (the machinery of ``benchmark/scopes.py``), a group and a pass at a time.

- A pass is ``forward`` or ``backward`` as ``scopes.phase_of`` reads them,
  with what a ``jax.checkpoint`` runs again (``rematted_computation`` in the
  ``op_name``, as ``recompute_ms_per_step`` reads it) taken out of backward
  as ``recomputed``.
- A name is matched as a **whole component** of the ``op_name`` (the parts
  between ``/``, less the ``vmap(`` / ``jvp(`` / ``transpose(`` JAX wraps
  around one): ``dpwa.attn.gqa`` and ``dpwa.attn.latent`` share a prefix, and
  a later name must not fall into an earlier one.
- An instruction under several names of the table (``dpwa.ssm/dpwa.ssm.scan``,
  a ``dpwa.moe.*`` inside another) is booked once, to the outermost.
- ``other`` is what lies under ``dpwa.forward`` and under no name of the
  table: the outer norms, the embedding, the residual adds, and a loss that
  carries no name.  So the groups and ``other`` partition the forward and
  backward phases of ``scopes.book``.

The accepted ``moe_scopes`` / ``latent_scopes`` / ``ssm_scopes`` each book
their own fixed names by substring; a reader with other names hands its own
table to :func:`ms_per_step` here.

    python benchmark/block_scopes.py <file.xplane.pb> [traced steps]

prints groups x passes in ms a step (steps counted from the trace's
``bench.step_call`` spans where not given), and under each group its largest
operations by the event's own name.
"""

from __future__ import annotations

import functools
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import scopes, tracered  # noqa: E402

# group -> the names of ``dpwa_tpu/utils/scopes.py`` that make it (written
# out, because the benchmark must read a program that lacks the newer ones;
# ``tests/yardstick/test_yardstick_block.py`` holds them to the program's).
# The scan's hand-written gradient names ``dpwa.ssm.scan`` and no mixer.
GROUPS = {
    "attn_gqa": ("dpwa.attn.gqa",),
    "attn_latent": ("dpwa.attn.latent",),
    "mlp": ("dpwa.mlp",),
    "moe_route": ("dpwa.moe.route",),
    "moe_experts": ("dpwa.moe.experts",),
    "moe_shared": ("dpwa.moe.shared",),
    "ssm": ("dpwa.ssm", "dpwa.ssm.scan"),
    "head": ("dpwa.head",),
    "loss": ("dpwa.loss",),
}
OTHER = "other"
PASSES = ("forward", "backward", "recomputed")
RECOMPUTED = "rematted_computation"
_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def components(op_name: str) -> list:
    """The parts of an ``op_name`` between ``/``, each without the
    transformations JAX wraps around it; of names the compiler joined with
    ``;`` the first is read, as ``scopes.phase_of`` does."""
    first = op_name.partition(";")[0]
    return [_WRAPPED.sub(r"\1", part) for part in first.split("/")]


def _by_name(groups: dict) -> dict:
    return {name: group for group, names in groups.items() for name in names}


def _place(op_name: str, by_name: dict):
    phase = scopes.phase_of(op_name)
    if phase not in ("forward", "backward"):
        return None
    parts = components(op_name)
    group = next((by_name[part] for part in parts if part in by_name), OTHER)
    return group, "recomputed" if RECOMPUTED in parts else phase


def place_of(op_name: str, groups: dict):
    """``(group, pass)`` of an instruction under ``dpwa.forward``, the group
    :data:`OTHER` under no name of ``groups``; None outside the forward
    scope."""
    return _place(op_name, _by_name(groups))


def booked(ops, window, groups: dict):
    """``(group, pass, event, self seconds)`` of each of one chip's events
    under ``dpwa.forward`` inside ``window``."""
    by_name = _by_name(groups)
    for event, own in scopes.self_times_in(ops, window):
        place = _place(event.detail, by_name)
        if place:
            yield (*place, event, own)


def book(ops, window, groups: dict = GROUPS) -> dict:
    """``{group: {pass: self seconds}}`` of one chip's events, :data:`OTHER`
    among the groups."""
    seconds = {
        group: dict.fromkeys(PASSES, 0.0) for group in (*groups, OTHER)
    }
    for group, of_pass, _, own in booked(ops, window, groups):
        seconds[group][of_pass] += own
    return seconds


def paced_ops(path: str, trace=None):
    """``(events, window)`` of the chip whose phases sum highest in the trace
    at ``path``; ``(None, window)`` where no event lies under a scope."""
    window = (trace or tracered.load(path)).window
    chips = scopes.scoped_ops(path)
    chip, _ = scopes.pace_setter(
        {dev: scopes.book(ops, window) for dev, ops in chips.items()}
    )
    return (None if chip is None else chips[chip]), window


def seconds_in(path: str, groups: dict = GROUPS, trace=None):
    """:func:`book` of the chip that sets the pace; None where the program
    has no scopes."""
    ops, window = paced_ops(path, trace)
    return None if ops is None else book(ops, window, groups)


@functools.lru_cache(maxsize=2)
def _of_window(window, root, table):
    """:func:`seconds_in` of the traced run whose file under ``root`` reduces
    to exactly ``window``, newest first (a reader is handed the reduced trace
    and no path); ``table`` is the groups' items, so that one reading serves
    every reader of one table.  None when no file does."""
    found = [
        os.path.join(d, f) for d, _, files in os.walk(root)
        for f in files if f.endswith(".xplane.pb")
    ]
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        trace = tracered.load(path)
        if tuple(trace.window) == window:
            return seconds_in(path, dict(table), trace)
    return None


def ms_per_step(trace, record, group: str, groups: dict = GROUPS):
    """What a reader returns: ms of ``group`` (or :data:`OTHER`) a traced
    step, its passes together; None where there is no trace, no traced step,
    no file, no scope, or nothing under the group's names."""
    if trace is None or not record["traced_steps"] or not trace.device_ops:
        return None
    seconds = _of_window(
        tuple(trace.window), scopes.TRACE_ROOT, tuple(groups.items())
    )
    total = sum(seconds[group].values()) if seconds else 0.0
    return 1e3 * total / record["traced_steps"] if total else None


def describe(path: str, steps=None, largest: int = 5) -> None:
    """Print the table of :data:`GROUPS` by pass in ms a step, each group's
    largest operations under it, and what the table must close on."""
    trace = tracered.load(path)
    steps = int(steps) if steps else sum(
        span.name == "bench.step_call" for span in trace.host_spans
    )
    ops, window = paced_ops(path, trace)
    if ops is None or not steps:
        print("no event lies under a dpwa.* scope, or no traced step")
        return
    per_step = lambda s: 1e3 * s / steps
    seconds = book(ops, window)
    folded = {group: {} for group in seconds}
    for group, _, event, own in booked(ops, window, GROUPS):
        name = tracered.fold(event.name)
        folded[group][name] = folded[group].get(name, 0.0) + own
    print(f"BLOCK {path} steps {steps}, ms a step")
    print(f"{'group':12s}" + "".join(f"{p:>12s}" for p in (*PASSES, "all")))
    for group, by_pass in seconds.items():
        row = [*by_pass.values(), sum(by_pass.values())]
        print(f"{group:12s}" + "".join(f"{per_step(s):12.3f}" for s in row))
        ranked = sorted(folded[group].items(), key=lambda kv: -kv[1])
        for name, own in ranked[:largest]:
            print(f"{'':12s}{per_step(own):12.3f} {name}")
    phases = scopes.book(ops, window)
    total = sum(sum(by_pass.values()) for by_pass in seconds.values())
    print(f"SUM {per_step(total):.6f} forward + backward "
          f"{per_step(phases['forward'] + phases['backward']):.6f}")


if __name__ == "__main__":
    describe(*sys.argv[1:3])
