"""From the program's scopes to ms a phase: the rule on op_names, the booking
on hand-made events, the five readers on the old fixture (silent) and on a
scoped trace recorded on a v5e, and the mixed fusions of a compiled text."""

import glob
import importlib
import json
import os

import pytest
from yardstick_paths import BENCH, MANIFEST

from benchmark import scopes, tracered
from benchmark.tracered import Event, Trace

READERS = [phase + "_ms_per_step" for phase in scopes.PHASES]
FIXTURES = os.path.join(BENCH, "fixtures")
SCOPED = os.path.join(FIXTURES, "scoped")
STEP = "jit(train_step)/jit(_step)/"

# The op_names ISSUE 24 quotes and the ones the toy steps lower to
# (tests/test_scopes.py), with the phase each lies under.
OP_NAMES = [
    ("jit(_step)/vmap(jvp(dpwa.forward))/dot_general", "forward"),
    ("jit(_step)/vmap(transpose(jvp(dpwa.forward)))/dot_general", "backward"),
    (STEP + "shard_map/jvp(dpwa.forward)/conv_general_dilated", "forward"),
    (STEP + "shard_map/transpose(jvp(dpwa.forward))/dot_general", "backward"),
    (STEP + "vmap(transpose(vmap(jvp(dpwa.forward))))/transpose", "backward"),
    (STEP + "transpose(jvp(dpwa.forward))/checkpoint/rematted_computation/mul",
     "backward"),
    (STEP + "vmap(jvp(dpwa.forward))/jit(take_along_axis)/gather", "forward"),
    (STEP + "vmap(jvp(dpwa.forward))/custom_vjp_call/pallas_call", "forward"),
    (STEP + "vmap(dpwa.optimizer)/add", "optimizer"),
    (STEP + "shard_map/dpwa.optimizer/mul", "optimizer"),
    (STEP + "dpwa.exchange/gather", "exchange"),
    (STEP + "shard_map/dpwa.exchange/cond/branch_3_fun/ppermute", "exchange"),
    (STEP + "dpwa.exchange/jit(_randint)/while/body/closed_call/add",
     "exchange"),
    (STEP + "vmap(jvp(dpwa.forward))/reshape;" + STEP + "dpwa.exchange/mul",
     "forward"),  # names the compiler joined: the first is read
    (STEP + "add", None),
    (STEP + "shard_map/add", None),
    ("", None),
]


@pytest.mark.parametrize("op_name,phase", OP_NAMES)
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


def test_a_conditional_does_not_count_its_collectives_twice():
    exchange = STEP + "shard_map/dpwa.exchange/cond"
    ops = [
        ev("fusion.1", 0, 2, STEP + "shard_map/jvp(dpwa.forward)/dot_general"),
        ev("conditional.1", 2, 10, exchange),
        ev("collective-permute-start.1", 2.5, 3, exchange + "/branch_1_fun/ppermute"),
        ev("collective-permute-done.1", 3, 6, exchange + "/branch_1_fun/ppermute"),
        ev("collective-permute-done.2", 6, 9, exchange + "/branch_1_fun/ppermute"),
    ]
    seconds = scopes.book(ops, (0.0, 10.0))
    assert seconds["exchange"] == pytest.approx(8.0)  # not 8 + 6.5
    assert seconds["forward"] == pytest.approx(2.0)
    assert sum(seconds.values()) == pytest.approx(10.0)


def test_an_event_without_an_op_name_is_unscoped():
    ops = [
        ev("copy-start.3", 0, 1), ev("copy-done.3", 1, 4),
        ev("slice-done.9", 4, 5),
        ev("fusion.2", 5, 6, STEP + "add"),  # the clock: under no scope
        ev("fusion.3", 6, 8, STEP + "vmap(dpwa.optimizer)/add"),
    ]
    seconds = scopes.book(ops, (0.0, 8.0))
    assert seconds["unscoped"] == pytest.approx(6.0)
    assert seconds["optimizer"] == pytest.approx(2.0)


def test_the_five_sum_to_the_union_of_op_time_inside_the_window():
    fwd, bwd = "vmap(jvp(dpwa.forward))/x", "vmap(transpose(jvp(dpwa.forward)))/x"
    ops = [
        ev("fusion.0", -3, 1, fwd),  # straddles the window's start
        ev("while.1", 2, 9, bwd), ev("fusion.4", 3, 5, bwd),
        ev("copy-done.1", 5, 6), ev("fusion.5", 7, 8.5, "dpwa.exchange/add"),
        ev("fusion.6", 11, 14, "vmap(dpwa.optimizer)/add"),  # and its end
        ev("fusion.7", 20, 21, fwd),  # outside
    ]
    window = (0.0, 12.0)
    seconds = scopes.book(ops, window)
    trace = Trace({0: ops}, [], window)
    assert sum(seconds.values()) == pytest.approx(tracered.busy_seconds(trace)[0])
    assert seconds == pytest.approx(dict(
        forward=1.0, backward=4.5, unscoped=1.0, exchange=1.5, optimizer=1.0,
    ))


def test_flash_kernels_are_booked_by_their_op_name_like_any_instruction():
    # On a v5e the library kernels carry one (my chip run, PR 24).
    flash = STEP + "vmap(%s)/Llama/layer_0/attn/jit(flash_attention)/"
    ops = [
        ev("flash_attention.4", 0, 2, flash % "jvp(dpwa.forward)" + "pallas_call"),
        ev("flash_mha_bwd_dq_block_q_major_128.9", 2, 5,
           flash % "transpose(jvp(dpwa.forward))" + "flash_mha_bwd_dq/pallas_call"),
        ev("flash_mha_bwd_dkv_block_q_major_128.9", 5, 9,
           flash % "transpose(jvp(dpwa.forward))" + "flash_mha_bwd_dkv/pallas_call"),
        ev("custom-call.7", 9, 10),
    ]
    assert scopes.book(ops, (0.0, 10.0)) == pytest.approx(dict(
        forward=2.0, backward=7.0, unscoped=1.0, optimizer=0.0, exchange=0.0,
    ))


def test_the_chip_that_sets_the_pace_and_a_program_without_scopes():
    slow = dict.fromkeys(scopes.PHASES, 1.0)
    fast = dict(slow, exchange=0.5)
    assert scopes.pace_setter({0: fast, 3: slow}) == (3, slow)
    bare = dict(dict.fromkeys(scopes.PHASES, 0.0), unscoped=9.0)
    assert scopes.pace_setter({0: bare}) == (None, None)
    assert scopes.pace_setter({}) == (None, None)


HLO = """HloModule jit__step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(_step)/vmap(transpose(jvp(dpwa.forward)))/mul"}
  ROOT %a = f32[8]{0} add(%m, %p0), metadata={op_name="jit(_step)/vmap(dpwa.optimizer)/add"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%p0), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/neg"}
}

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(_step)/vmap(dpwa.optimizer)/add"}
  ROOT %g = f32[8]{0} multiply(%a, %p0), metadata={op_name="jit(_step)/dpwa.exchange/mul"}
}

%fused_computation.4 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %inner = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.3
  ROOT %e = f32[8]{0} exponential(%inner), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/exp"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.params"}
  %multiply_add_fusion = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/vmap(transpose(jvp(dpwa.forward)))/mul"}
  %fusion.2 = f32[8]{0} fusion(%multiply_add_fusion), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/neg"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.2)
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  ROOT %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/exp"}
}
"""


def test_mixed_fusions_of_a_compiled_text():
    assert scopes.mixed_fusions(HLO) == [
        ("multiply_add_fusion", "backward", ["backward", "optimizer"]),
        ("fusion.3", "unscoped", ["exchange", "optimizer"]),
        # A fusion inside a fusion is no event of its own, and counts for
        # the one that holds it.
        ("fusion.4", "forward", ["exchange", "forward", "optimizer"]),
    ]


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: a varint for an int, length-delimited otherwise."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_op_names_are_read_from_the_event_metadata(tmp_path):
    entry = lambda key, message: field(1, key) + field(2, message)
    stat_names = {1: "hlo_category", 26: "tf_op", 300: "jit(_step)/add:"}
    plane = lambda name, metadata: field(1, field(2, name) + b"".join(
        field(5, entry(i, field(1, i) + field(2, n)))
        for i, n in stat_names.items()
    ) + b"".join(field(4, entry(i, m)) for i, m in enumerate(metadata)))
    forward = "jit(_step)/vmap(jvp(dpwa.forward))/dot_general"
    device = plane("/device:TPU:2", [
        field(1, 7) + field(2, "%fusion.7 = f32[8] fusion(...)") + field(
            5, field(1, 1) + field(5, "loop fusion")
        ) + field(5, field(1, 26) + field(5, forward + ":")),
        # A string the profiler keeps once, among the stat names.
        field(2, "%add.1 = f32[] add(...)") + field(5, field(1, 26) + field(7, 300)),
        field(2, "%copy-start.3 = (...) copy-start(...)") + field(
            5, field(1, 1) + field(5, "async-copy")
        ),
    ])
    host = plane("/host:CPU", [
        field(2, "bench.step_call") + field(5, field(1, 26) + field(5, "x:")),
    ])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(device + host)
    assert scopes.op_names(str(path)) == {2: {
        "%fusion.7 = f32[8] fusion(...)": forward,
        "%add.1 = f32[] add(...)": "jit(_step)/add",
    }}


EMPTY = dict(
    traced_steps=0, blocks=[], dispatch_ms=[], block_steps=1, leaf_sizes=[],
    cell={"wire_dtype": "f32"}, flops_per_sample=0.0, state_setup_s=None,
    compile_s=None, kernel_work=None, device_kind="TPU v5 lite",
)


@pytest.mark.parametrize("name", READERS)
def test_reader_says_nothing_without_a_trace_or_a_scope(name):
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert "workloads" not in entry  # every cell runs a scoped step
    assert reader.reduce(None, dict(EMPTY)) is None
    (old,) = glob.glob(os.path.join(FIXTURES, "*.xplane.pb"))
    trace = tracered.load(old)
    # PR 22's fixture: not under benchmark/out/trace, and no scope in it.
    assert reader.reduce(trace, dict(EMPTY, traced_steps=2)) is None
    assert scopes.pace_setter(scopes.phase_seconds(old, trace)) == (None, None)
    assert reader.reduce(trace, dict(EMPTY)) is None


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(SCOPED, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded scoped trace in benchmark/fixtures/scoped")
    (path,) = paths
    with open(os.path.join(SCOPED, "expected.json")) as f:
        return path, json.load(f)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_scoped_trace(name, recorded, monkeypatch):
    path, expected = recorded
    # A reader is handed the reduced trace and no path: it finds the file
    # again by its window, under the directory run.py traces into.
    monkeypatch.setattr(scopes, "TRACE_ROOT", SCOPED)
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    record = dict(EMPTY, traced_steps=expected["traced_steps"])
    got = reader.reduce(tracered.load(path), record)
    assert got == pytest.approx(expected["ms_per_step"][name], rel=1e-9)
    # Another window is another run: nothing is read from this file for it.
    other = tracered.load(path)._replace(window=(0.0, 1.0))
    assert reader.reduce(other, record) is None


def test_recorded_scoped_trace_books_every_event_once(recorded):
    path, expected = recorded
    trace = tracered.load(path)
    chip, seconds = scopes.pace_setter(scopes.phase_seconds(path, trace))
    assert str(chip) == expected["chip"]
    assert sum(seconds.values()) == pytest.approx(
        tracered.busy_seconds(trace)[chip], rel=1e-9
    )
    assert all(seconds[phase] > 0 for phase in scopes.PHASES)
    ops = scopes.scoped_ops(path)[chip]
    with_name = sum(bool(e.detail) for e in ops)
    assert with_name / len(ops) == pytest.approx(
        expected["events_with_op_name_share"], abs=1e-9
    )
    # The copies and slices the compiler adds carry no op_name.
    assert not any(
        e.detail for e in ops
        if tracered.fold(e.name) in ("copy-start", "copy-done", "slice-done")
    )
