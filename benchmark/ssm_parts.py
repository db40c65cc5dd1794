#!/usr/bin/env python3
"""What a state-space mixer does around its scan, by the names the program
gives it (``dpwa_tpu/utils/scopes.SSM_PARTS``, inside ``dpwa.ssm`` and beside
``dpwa.ssm.scan``): the four projections with their adapters, and the
pointwise passes between them (the convolution with silu, the inner norms and
the float32 step size, the gate).  :data:`GROUPS` is the table, built from the
program's own constants and handed to ``benchmark/block_scopes.py``'s booking
(a name a whole component of the ``op_name``, an instruction under two names
booked once); the accepted ``ssm_mixer_ms_per_step`` and
``ssm_scan_ms_per_step`` keep reading ``benchmark/ssm_scopes.py``, by
substring.  :func:`book` adds :data:`LEFT`: what ``ssm_scopes`` books under
``dpwa.ssm`` and no name of the table holds, so that the parts sum to the
mixer.

The work each group *requires* is counted from shapes alone
(:func:`projections_required`, :func:`pointwise_required`).  ``record``
carries the traffic and no configuration, and ``kernel_work`` is filled by an
accepted builder, so :func:`cell_files` finds the configuration through
``BENCHMARK.json`` by the cells a metric's own entry lists.

    python benchmark/ssm_parts.py <file.xplane.pb> [traced steps]
    python benchmark/ssm_parts.py --mixed <workload> [file.xplane.pb [steps]]

The first prints names x passes in ms a step, under each name **every**
operation over 0.5 ms a step by its folded name and the component that
follows the name in its ``op_name`` (a flax module: ``in_proj``,
``dt_norm``; or the primitive itself), then ``left`` and the sum beside what
``ssm_scopes`` reads for the mixer, with the FLOPs and bytes a step that the
projections and the pointwise passes require at the cell's shapes beside
their groups.  The second compiles the cell's step for a
described v5e (nothing runs) and counts the fusions whose instructions carry
two of the names: a fusion's time is booked whole to the name of its own
``op_name``, so this is how far to trust the split; with the trace of a run
of the same step, each kind of them in ms a step.
"""

from __future__ import annotations

import collections
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    block_scopes, flops_ssm, latent_scopes, scopes, ssm_scopes, tracered,
)
from benchmark.flops_latent import _adapter_values, _values  # noqa: E402
from dpwa_tpu.utils import scopes as program  # noqa: E402


def _groups() -> dict:
    """A program from before the four names has the scan's alone."""
    parts = getattr(program, "SSM_PARTS", None)
    named = {
        "ssm_proj": (parts.proj,),
        "ssm_pointwise": (parts.conv, parts.dt, parts.gate),
    } if parts else {}
    return {**named, "ssm_scan": (program.SSM_SCAN,)}


# group -> the names of ``dpwa_tpu/utils/scopes.py`` that make it.
GROUPS = _groups()
# The same names a row each, for the printed table.
NAMES = {name: (name,) for names in GROUPS.values() for name in names}
LEFT = "left"
MIXER = ssm_scopes.GROUPS["ssm_mixer"]
# Operations a (token, channel) of the pointwise passes, forward, at ``K``
# taps: the taps' multiply-adds and the bias ``2 K + 1``, silu 4 (exp, add,
# divide, multiply) twice, ``dt_bias`` and softplus 4, the gate's product 1.
POINTWISE_OPS = 14
# An inner norm a column: square, its part of the sum, the scale, the weight.
NORM_OPS = 4
LARGE_MS = 0.5


def book(ops, window, table: dict = GROUPS) -> dict:
    """``{part: {pass: self seconds}}`` of one chip's events: the rows of
    ``table`` and :data:`LEFT`."""
    seconds = {
        part: dict.fromkeys(block_scopes.PASSES, 0.0)
        for part in (*table, LEFT)
    }
    for part, of_pass, _, own in _booked(ops, window, table):
        seconds[part][of_pass] += own
    return seconds


def _booked(ops, window, table: dict):
    """``block_scopes.booked`` of what lies under the mixer's name: what the
    table does not hold is :data:`LEFT`, what ``ssm_scopes`` would not book
    is dropped."""
    for part, of_pass, event, own in block_scopes.booked(ops, window, table):
        if part != block_scopes.OTHER:
            yield part, of_pass, event, own
        elif MIXER in event.detail.partition(";")[0]:
            yield LEFT, of_pass, event, own


def ms_per_step(trace, record, group: str):
    """ms of ``group`` a traced step, its passes together; None as
    ``block_scopes.ms_per_step`` gives it, and for a program without the
    group's names."""
    if group not in GROUPS:
        return None
    return block_scopes.ms_per_step(trace, record, group, GROUPS)


def projections_required(
    config: dict, tokens: int, peers: int, rank: int,
    base_bytes: int = 2, adapter_bytes: int = 4,
) -> dict:
    """What the mixers' four projections of one training step must do over
    ``tokens`` tokens (all peers') with ``peers`` copies of the weights,
    whatever implements them, as ``flops_moe.moe_experts_required`` counts an
    expert's.  FLOPs: the frozen kernels forward and to the activations (no
    base-weight gradient), the adapters forward, to the activations and to
    themselves.  HBM bytes: every weight once a pass (kernels: 2 passes in
    the base type; adapters: 3, float32) and each projection's rows in and
    out once a pass (3 passes: forward, to the activations, to the adapters)
    in the stream's ``compute_dtype``.  A recomputed block's second forward
    is not counted.  On a v5e the FLOPs bound, 3 to 1 at the published
    sizes."""
    shapes = flops_ssm.mamba_projections(config)
    layers = flops_ssm.layer_kinds(config)["mamba"]
    kernel, adapter = _values(shapes), _adapter_values(shapes, rank)
    stream = flops_ssm.STREAM_BYTES[config["assumed"]["compute_dtype"]]
    return dict(
        flops=float(layers * tokens * 2 * (2 * kernel + 3 * adapter)),
        bytes=float(layers * (
            peers * (2 * kernel * base_bytes + 3 * adapter * adapter_bytes)
            + 3 * tokens * sum(a + b for a, b in shapes.values()) * stream
        )),
    )


def pointwise_required(config: dict, tokens: int) -> dict:
    """What one training step's passes between the projections must do over
    ``tokens`` tokens, whatever implements them: every operand read and every
    result written once a pass, in the types the program hands over
    (``delta`` and its gradient float32, the rest the stream's
    ``compute_dtype``, ``s`` bytes), over ``E`` channels and the ``C = R + 2
    N`` columns of ``x_proj``'s product.

    Forward, ``E (6 s + 4) + 2 C s`` bytes a token a layer: the convolution
    with its bias and silu reads ``x`` and writes it (``2 E s``); the three
    norms read and write their columns (``2 C s``); ``dt_bias`` and softplus
    read ``dt_proj``'s product and write ``delta`` (``E s + 4 E``); the gate
    reads ``y``, ``z`` and writes their product (``3 E s``).  Backward, ``E
    (11 s + 4) + 3 C s``: the gate reads its gradient, ``y`` and ``z`` and
    writes ``dy``, ``dz`` (``5 E s``); softplus reads ``ddelta`` and its
    input and writes one gradient (``4 E + 2 E s``); the norms read their
    gradient and their input and write one (``3 C s``); the convolution reads
    the two gradients that meet in ``x`` (the scan's, ``x_proj``'s) and ``x``
    and writes one (``4 E s``).  217 kB a token a layer at the published
    sizes.  The parameters (taps, biases, norm weights, ``A_log``: a few
    thousand values a layer, no gradient under LoRA), the split of a product
    (a view) and a recomputed block's second forward are not counted.
    FLOPs: :data:`POINTWISE_OPS` ``+ 2 K`` a (token, channel) and
    :data:`NORM_OPS` a column forward, twice that backward.  On a v5e the
    bytes bound, 150 to 1."""
    e, taps = flops_ssm.inner_channels(config), config["mamba_d_conv"]
    columns = config["mamba_dt_rank"] + 2 * config["mamba_d_state"]
    layers = flops_ssm.layer_kinds(config)["mamba"]
    s = flops_ssm.STREAM_BYTES[config["assumed"]["compute_dtype"]]
    forward = e * (6 * s + 4) + 2 * columns * s
    backward = e * (11 * s + 4) + 3 * columns * s
    ops = (POINTWISE_OPS + 2 * taps) * e + NORM_OPS * columns
    return dict(
        flops=float(3 * ops * tokens * layers),
        bytes=float((forward + backward) * tokens * layers),
    )


def cell_files(metric: str = "ssm_proj_roofline"):
    """``(configuration file, traffic file)`` of the cells that ``metric``'s
    entry in ``BENCHMARK.json`` lists; None where there is no such entry, no
    list, or the cells name more than one configuration or traffic."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = next(
        (m.get("workloads") for m in manifest["per_layer"]
         if m["name"] == metric), None,
    ) or ()
    named = {
        (w["config"], w["traffic"]) for w in manifest["workloads"]
        if w["name"] in cells
    }
    if len(named) != 1:
        return None
    ((config, traffic),) = named
    (file,) = (c["file"] for c in manifest["configs"] if c["name"] == config)
    paths = (file, os.path.join("benchmark", "workloads", traffic + ".json"))
    found = []
    for path in paths:
        with open(os.path.join(ROOT, path)) as f:
            found.append(json.load(f))
    return tuple(found)


def required(group: str, config: dict, cell: dict) -> dict:
    """``{"flops", "bytes"}`` a step of ``group`` at the configuration's
    widths and the tokens a step of ``cell`` (a traffic file, or the one a
    run's ``record`` carries)."""
    tokens = cell["peers"] * cell["per_peer_batch"] * cell["seq_len"]
    if group == "ssm_proj":
        return projections_required(
            config, tokens, cell["peers"], config["assumed"]["lora"]["rank"]
        )
    return pointwise_required(config, tokens)


def roofline(trace, record, group: str, metric: str):
    """100 x the least time the chip could take for what ``group`` requires a
    step over the time under its names the trace shows, a recomputed block's
    second forward included; None where either is missing."""
    ms, files = ms_per_step(trace, record, group), cell_files(metric)
    if ms is None or files is None:
        return None
    work = required(group, files[0], record["cell"])
    return latent_scopes.roofline_share(
        dict(record, kernel_work={group: work}), group, 1e-3 * ms
    )


def part_of(op_name: str):
    """The outermost name of :data:`NAMES` among an ``op_name``'s
    components, or None."""
    return next(
        (c for c in block_scopes.components(op_name) if c in NAMES), None
    )


def mixed_parts(compiled_text: str) -> list:
    """``scopes.mixed_fusions`` with a part's name where it reads a phase:
    ``[(fusion, the name its own op_name books it to or "unscoped", the
    sorted names among the instructions it fused)]`` for every fusion that
    runs as an instruction of its own and fused instructions of two or more
    of :data:`NAMES`."""
    with mock.patch.object(scopes, "phase_of", part_of):
        return scopes.mixed_fusions(compiled_text)


def mixed_kinds(compiled_text: str) -> dict:
    """``{fusion: kind}`` of :func:`mixed_parts`, a kind being the names
    mixed and the one booked."""
    return {
        fusion: f"{'+'.join(names)} booked as {booked_to}"
        for fusion, booked_to, names in mixed_parts(compiled_text)
    }


def mixed_seconds(kinds: dict, path: str) -> dict:
    """``{kind: self seconds}`` of the fusions of :func:`mixed_kinds` in the
    trace at ``path`` of a run of the same step, with ``""`` for every event
    together; events and instructions are matched by name, as
    ``scopes.describe_mixed`` matches them."""
    ops, window = block_scopes.paced_ops(path)
    seconds = {"": 0.0}
    for event, own in scopes.self_times_in(ops or [], window):
        seconds[""] += own
        if event.name in kinds:
            kind = kinds[event.name]
            seconds[kind] = seconds.get(kind, 0.0) + own
    return seconds


def _traced_steps(trace, steps=None) -> int:
    """The steps a trace holds: as given, or its ``bench.step_call`` spans."""
    return int(steps) if steps else sum(
        span.name == "bench.step_call" for span in trace.host_spans
    )


def describe_mixed(workload: str, path=None, steps=None) -> None:
    """Print the count of the cell's mixed fusions by kind and, given the
    trace of a run of the same step, each kind's ms a step."""
    kinds = mixed_kinds(scopes.compiled_step_text(workload))
    print("MIXED_PARTS", workload, len(kinds),
          json.dumps(collections.Counter(kinds.values()), sort_keys=True))
    if path:
        steps = _traced_steps(tracered.load(path), steps)
        print("MIXED_PARTS_MS", json.dumps({
            kind: round(1e3 * s / steps, 3)
            for kind, s in sorted(mixed_seconds(kinds, path).items())
        }))


def describe(path: str, steps=None) -> None:
    trace = tracered.load(path)
    steps = _traced_steps(trace, steps)
    ops, window = block_scopes.paced_ops(path, trace)
    if ops is None or not steps:
        print("no event lies under a dpwa.* scope, or no traced step")
        return
    passes = block_scopes.PASSES
    # part -> (folded name, the component after the part's) -> pass -> seconds
    found = {part: {} for part in (*NAMES, LEFT)}
    for part, of_pass, event, own in _booked(ops, window, NAMES):
        parts = block_scopes.components(event.detail)
        after = parts[parts.index(part) + 1] if part in parts else ""
        by_pass = found[part].setdefault(
            (tracered.fold(event.name), after), dict.fromkeys(passes, 0.0)
        )
        by_pass[of_pass] += own
    seconds = {
        part: {p: sum(by_pass[p] for by_pass in named.values()) for p in passes}
        for part, named in found.items()
    }
    per_step = lambda s: 1e3 * s / steps
    row = lambda by_pass: "".join(
        f"{per_step(s):12.3f}" for s in (*by_pass.values(), sum(by_pass.values()))
    )
    print(f"SSM_PARTS {path} steps {steps}, ms a step")
    print(f"{'part':16s}" + "".join(f"{p:>12s}" for p in (*passes, "all")))
    for part, by_pass in seconds.items():
        print(f"{part:16s}{row(by_pass)}")
        ranked = sorted(found[part].items(), key=lambda kv: -sum(kv[1].values()))
        for (name, after), op_pass in ranked:
            if per_step(sum(op_pass.values())) > LARGE_MS:
                print(f"{'':16s}{row(op_pass)} {name} {after}")
    files = cell_files()
    for group, names in GROUPS.items():
        total = sum(sum(seconds[name].values()) for name in names)
        work = required(group, *files) if files and group != "ssm_scan" else {}
        print(f"GROUP {group} {per_step(total):.6f}" + "".join(
            f" {key} required {value:.6g}" for key, value in work.items()
        ))
    total = sum(sum(by_pass.values()) for by_pass in seconds.values())
    mixer = ssm_scopes.book(ops, window)["ssm_mixer"]
    print(f"SUM {per_step(total):.6f} of which left "
          f"{per_step(sum(seconds[LEFT].values())):.6f}; "
          f"ssm_scopes' mixer {per_step(mixer):.6f}")


if __name__ == "__main__":
    if sys.argv[1] == "--mixed":
        describe_mixed(*sys.argv[2:5])
    else:
        describe(*sys.argv[1:3])
