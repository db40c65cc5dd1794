"""The mixer's parts' yardstick: ``ssm_parts``' table held to the program's
names, its booking on hand-made events (projections, pointwise passes, scan
and what is left, by pass, summing to what ``ssm_scopes`` books under
``dpwa.ssm``), the required work by hand at the cell's shapes, the four
readers' arithmetic and their silence on a program without the names, the
mixed fusions of a hand-made compiled text, and the three manifest entries."""

import glob
import importlib
import os

import pytest
from yardstick_paths import BENCH, MANIFEST, cell_files

from benchmark import block_scopes, scopes, ssm_parts, ssm_scopes, tracered
from benchmark.tracered import Event, Trace

CELL = "jamba2-lora-period14-stacked2"
READERS = {  # metric -> (the group it reads, unit, better)
    "ssm_proj_ms_per_step": ("ssm_proj", "ms", "lower"),
    "ssm_proj_roofline": ("ssm_proj", "%", "higher"),
    "ssm_pointwise_ms_per_step": ("ssm_pointwise", "ms", "lower"),
}
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/"
BWD = (
    STEP + "vmap(transpose(vmap(jvp(dpwa.forward))))/jvp(dpwa.forward)/"
    "checkpoint/"
)
MIXER = "layer_1/mamba/dpwa.ssm/"
AGAIN = BWD + "rematted_computation/" + MIXER


@pytest.fixture(scope="module")
def files():
    _, config, cell = cell_files(CELL)
    return config, cell


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


OPS = [
    ev("fusion.1", 0, 2, FWD + "dpwa.ssm.proj/in_proj/dot_general"),
    ev("fusion.2", 2, 3, FWD + "dpwa.ssm.conv/mul"),
    ev("fusion.3", 3, 4, FWD + "dpwa.ssm.dt/dt_norm/rsqrt"),
    ev("dpwa_selective_scan_fwd.4", 4, 7, FWD + "dpwa.ssm.scan/pallas_call"),
    ev("fusion.5", 7, 8, FWD + "dpwa.ssm.gate/mul"),
    # Under the mixer's name and under none of the five.
    ev("copy.6", 8, 9, FWD + "copy"),
    ev("fusion.7", 9, 10,
       STEP + "vmap(jvp(dpwa.forward))/Llama/layer_1/dpwa.mlp/mlp/w_up/dot_general"),
    ev("fusion.8", 10, 12, AGAIN + "dpwa.ssm.proj/x_proj/dot_general"),
    ev("fusion.9", 12, 13, AGAIN + "dpwa.ssm.dt/jit(softplus)/logaddexp"),
    # The scan's hand-written gradient names its own scope and no mixer.
    ev("dpwa_selective_scan_bwd.10", 13, 18,
       BWD + "layer_1/mamba/dpwa.ssm.scan/pallas_call"),
    ev("fusion.11", 18, 21, BWD + MIXER + "dpwa.ssm.proj/out_proj/transpose"),
    ev("fusion.12", 21, 23, BWD + MIXER + "dpwa.ssm.gate/mul"),
    # A longer name is another name: a whole component or nothing.
    ev("fusion.13", 23, 24, BWD + MIXER + "dpwa.ssm.projection/mul"),
    ev("fusion.14", 24, 25, STEP + "dpwa.exchange/mul"),
    ev("fusion.15", 30, 33, FWD + "dpwa.ssm.proj/in_proj/dot_general"),
]
WINDOW = (0.0, 26.0)
BOOKED = {  # part -> forward, backward, recomputed
    "ssm_proj": (2, 3, 2), "ssm_pointwise": (3, 2, 1), "ssm_scan": (3, 5, 0),
    "left": (1, 1, 0),
}


def test_the_tables_names_are_the_programs():
    from dpwa_tpu.utils import scopes as program

    parts = program.SSM_PARTS
    assert parts._fields == ("proj", "conv", "dt", "gate")
    assert tuple(parts) == (
        "dpwa.ssm.proj", "dpwa.ssm.conv", "dpwa.ssm.dt", "dpwa.ssm.gate",
    )
    assert ssm_parts.GROUPS == {
        "ssm_proj": (parts.proj,),
        "ssm_pointwise": (parts.conv, parts.dt, parts.gate),
        "ssm_scan": (program.SSM_SCAN,),
    }
    assert list(ssm_parts.NAMES) == [
        parts.proj, parts.conv, parts.dt, parts.gate, program.SSM_SCAN,
    ]
    # ``ssm_scopes`` matches by substring: the accepted scan reader must not
    # read a part, and the accepted mixer reader must read every one.
    for name in parts:
        assert ssm_scopes.GROUPS["ssm_scan"] not in name
        assert ssm_scopes.GROUPS["ssm_mixer"] in name
    # The accepted table books all five to the mixer, as before.
    for name in ssm_parts.NAMES:
        op = FWD + name + "/mul"
        assert block_scopes.place_of(op, block_scopes.GROUPS) == (
            "ssm", "forward"
        )


def test_a_program_from_before_the_names_has_the_scans_row_alone(monkeypatch):
    monkeypatch.delattr(ssm_parts.program, "SSM_PARTS")
    assert ssm_parts._groups() == {"ssm_scan": ("dpwa.ssm.scan",)}


def test_the_parts_are_booked_by_pass_and_sum_to_the_mixer():
    seconds = ssm_parts.book(OPS, WINDOW)
    assert list(seconds) == ["ssm_proj", "ssm_pointwise", "ssm_scan", "left"]
    for part, (forward, backward, recomputed) in BOOKED.items():
        assert seconds[part] == pytest.approx(dict(
            forward=forward, backward=backward, recomputed=recomputed
        )), part
    total = sum(sum(by_pass.values()) for by_pass in seconds.values())
    accepted = ssm_scopes.book(OPS, WINDOW)
    assert total == pytest.approx(accepted["ssm_mixer"]) == 23.0
    assert sum(seconds["ssm_scan"].values()) == pytest.approx(
        accepted["ssm_scan"]
    )
    # By name: the pointwise group's three rows apart.
    by_name = ssm_parts.book(OPS, WINDOW, ssm_parts.NAMES)
    assert {n: sum(s.values()) for n, s in by_name.items()} == pytest.approx({
        "dpwa.ssm.proj": 7.0, "dpwa.ssm.conv": 1.0, "dpwa.ssm.dt": 2.0,
        "dpwa.ssm.gate": 3.0, "dpwa.ssm.scan": 8.0, "left": 2.0,
    })


def readers():
    return {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        for name in READERS
    }


def patched(monkeypatch, ops):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: ops, 1: ops[:3]})
    trace = Trace({0: ops, 1: ops[:3]}, [], WINDOW)
    monkeypatch.setattr(
        block_scopes, "_of_window",
        lambda window, root, table: block_scopes.seconds_in(
            "unused", dict(table), trace
        ),
    )
    return trace


def record_of(cell, **changes):
    return dict(
        dict(traced_steps=2, device_kind="TPU v5 lite", kernel_work={},
             cell=cell), **changes
    )


def test_the_readers_on_a_small_scoped_trace(monkeypatch, files):
    config, cell = files
    trace = patched(monkeypatch, OPS)
    record = record_of(cell)
    read = {name: r.reduce(trace, record) for name, r in readers().items()}
    assert read["ssm_proj_ms_per_step"] == pytest.approx(3500.0)
    assert read["ssm_pointwise_ms_per_step"] == pytest.approx(3000.0)
    # The floor of the cell's shapes (the test below has it by hand) over
    # 3.5 s a step.
    proj = ssm_parts.projections_required(config, 8192, 2, 16)
    assert read["ssm_proj_roofline"] == pytest.approx(
        100 * proj["flops"] / 197e12 / 3.5
    )
    for name, reader in readers().items():
        assert reader.LAYER == "state-space mixer", name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(None, {}) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name


def test_a_roofline_needs_one_configuration(monkeypatch, files):
    config, cell = files
    trace = patched(monkeypatch, OPS)
    assert ssm_parts.cell_files() == (config, cell)
    # No entry of that name, an entry without a list, cells of two
    # configurations: no configuration, so no share and nothing raised.
    assert ssm_parts.cell_files("no_such_metric") is None
    assert ssm_parts.cell_files("device_idle_share") is None
    assert ssm_parts.cell_files("attn_ms_per_step") is None
    assert ssm_parts.roofline(
        trace, record_of(cell), "ssm_proj", "attn_ms_per_step"
    ) is None
    assert ssm_parts.roofline(
        trace, record_of(cell), "ssm_proj", "ssm_proj_roofline"
    ) > 0
    assert ssm_parts.required("ssm_pointwise", config, cell) == (
        ssm_parts.pointwise_required(config, 8192)
    )


def test_a_program_without_the_names_gives_nothing(monkeypatch, files):
    """The parent's program on this cell's step: ``dpwa.ssm`` and
    ``dpwa.ssm.scan`` alone, so the four readers return nothing and do not
    raise, with the table built from this program or from the parent's."""
    _, cell = files
    bare = [
        e._replace(detail=e.detail.replace(part + "/", ""))
        for e in OPS for part in ["dpwa.ssm.proj", "dpwa.ssm.conv",
                                  "dpwa.ssm.dt", "dpwa.ssm.gate"]
        if part + "/" in e.detail
    ] + [e for e in OPS if "dpwa.ssm.scan" in e.detail]
    bare.sort(key=lambda e: e.start)
    trace = patched(monkeypatch, bare)
    record = record_of(cell)
    for name, reader in readers().items():
        assert reader.reduce(trace, record) is None, name
    assert ssm_parts.ms_per_step(trace, record, "ssm_scan") > 0
    monkeypatch.setattr(ssm_parts, "GROUPS", {"ssm_scan": ("dpwa.ssm.scan",)})
    for name, reader in readers().items():
        assert reader.reduce(trace, record) is None, name
    monkeypatch.setattr(
        block_scopes, "_of_window", lambda window, root, table: None
    )
    assert ssm_parts.ms_per_step(trace, record, "ssm_scan") is None


@pytest.mark.parametrize("fixture", ["", "scoped"])
@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_traces_that_lack_its_names(
    name, fixture, monkeypatch, files
):
    """The ResNet steps recorded on a v5e, without scopes and with the four
    phases': neither holds a name these readers look for."""
    root = os.path.join(BENCH, "fixtures", fixture)
    paths = glob.glob(os.path.join(root, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded trace in " + root)
    (path,) = paths
    monkeypatch.setattr(scopes, "TRACE_ROOT", root)
    block_scopes._of_window.cache_clear()
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.reduce(tracered.load(path), record_of(files[1])) is None
    block_scopes._of_window.cache_clear()


def test_the_projections_required_work_by_hand(files):
    config, cell = files
    tokens = cell["peers"] * cell["per_peer_batch"] * cell["seq_len"]
    assert tokens == 8192
    work = ssm_parts.projections_required(config, tokens, 2, 16)
    # 2560x10240 + 5120x192 + 160x5120 + 5120x2560 frozen values a token,
    # 16 x (12,800 + 5,312 + 5,280 + 7,680) of adapters, 13 mixers.
    assert work["flops"] == 13 * 8192 * 2 * (2 * 41_123_840 + 3 * 497_152)
    assert work["flops"] == pytest.approx(17.84e12, rel=1e-3)
    assert work["flops"] / 197e12 == pytest.approx(90.5e-3, rel=1e-3)
    # The forward alone, a projection: ISSUE 55's 28.3, 1.06, 0.89, 14.2 ms.
    forward = lambda values: 13 * 8192 * 2 * values / 197e12
    assert [round(1e3 * forward(v), 2) for v in (
        26_214_400, 983_040, 819_200, 13_107_200,
    )] == [28.34, 1.06, 0.89, 14.17]
    # Two peers' bfloat16 kernels twice and float32 adapters three times;
    # rows in and out of the four projections three times, bfloat16.
    weights = 2 * (2 * 41_123_840 * 2 + 3 * 497_152 * 4)
    rows = 3 * 8192 * (12_800 + 5312 + 5280 + 7680) * 2
    assert work["bytes"] == 13 * (weights + rows)
    # The bound is FLOPs, 3 to 1: 90.5 ms a step against 29.7.
    assert work["bytes"] / 819e9 == pytest.approx(29.7e-3, rel=1e-2)
    wide = ssm_parts.projections_required(
        dict(config, assumed=dict(config["assumed"], compute_dtype="float32")),
        tokens, 2, 16,
    )
    assert wide["bytes"] == 13 * (weights + 2 * rows)


def test_the_pointwise_required_work_by_hand(files):
    config, cell = files
    work = ssm_parts.pointwise_required(config, 8192)
    # Forward over 5,120 channels: x in and out of the convolution, dt_proj's
    # product in and delta (float32) out, y and z in and the gate's product
    # out; the 192 columns in and out of their norms.
    forward = 5120 * (2 + 2) + 5120 * (2 + 4) + 5120 * (2 + 2 + 2) + 192 * (2 + 2)
    # Backward: the gate 5 passes; ddelta (float32) and softplus' input in,
    # one gradient out; the norms 3 passes; two gradients and x in, one out.
    backward = (
        5120 * 5 * 2 + 5120 * (4 + 2 + 2) + 192 * 3 * 2 + 5120 * 4 * 2
    )
    assert (forward, backward) == (82_688, 134_272)
    assert forward + backward == 216_960
    assert work["bytes"] == 216_960 * 8192 * 13
    # 22 operations a (token, channel) at 4 taps and 4 a normed column,
    # forward; twice that backward.
    assert work["flops"] == 3 * (22 * 5120 + 4 * 192) * 8192 * 13
    # The bound is bytes, 150 to 1: 28.2 ms a step against 0.18.
    assert work["bytes"] / 819e9 == pytest.approx(28.2e-3, rel=1e-2)
    assert 140 < (work["bytes"] / 819e9) / (work["flops"] / 197e12) < 160
    wide = ssm_parts.pointwise_required(
        dict(config, assumed=dict(config["assumed"], compute_dtype="float32")),
        8192,
    )
    assert wide["bytes"] == (5120 * 17 * 4 + 5120 * 8 + 192 * 5 * 4) * 8192 * 13


COMPILED = """\
HloModule jit__step

%fused_computation (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %silu.1 = bf16[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.gate/mul"}
  ROOT %dot.2 = bf16[8,8]{1,0} dot(%silu.1, %p0), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.proj/out_proj/dot_general"}
}

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %add.3 = bf16[8,8]{1,0} add(%p0, %p0), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.conv/add"}
}

%fused_computation.2 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %mul.4 = bf16[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/dpwa.mlp/mlp/mul"}
  ROOT %exp.5 = bf16[8,8]{1,0} exponential(%mul.4), metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.dt/exp"}
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.proj/out_proj/dot_general"}
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.conv/add"}
  ROOT %fusion.3 = bf16[8,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/vmap(jvp(dpwa.forward))/Llama/layer_1/mamba/dpwa.ssm/dpwa.ssm.dt/exp"}
}
"""


def test_a_fusion_of_two_parts_is_counted_and_booked_to_its_own_name():
    # The gate fused into out_proj's matmul; a fusion of one part and one of
    # a part with an instruction of no part are not mixed.
    assert ssm_parts.mixed_parts(COMPILED) == [
        ("fusion.1", "dpwa.ssm.proj", ["dpwa.ssm.gate", "dpwa.ssm.proj"]),
    ]
    assert ssm_parts.mixed_kinds(COMPILED) == {
        "fusion.1": "dpwa.ssm.gate+dpwa.ssm.proj booked as dpwa.ssm.proj",
    }
    # ``scopes.mixed_fusions`` reads phases as before.
    assert scopes.mixed_fusions(COMPILED) == []
    assert ssm_parts.part_of(FWD + "dpwa.ssm.scan/pallas_call") == "dpwa.ssm.scan"
    assert ssm_parts.part_of(FWD + "copy") is None
    assert ssm_parts.part_of(STEP + "dpwa.optimizer/mul") is None


def test_the_mixed_fusions_time_is_read_from_a_trace_by_name(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS})
    monkeypatch.setattr(
        tracered, "load", lambda path: Trace({0: OPS}, [], WINDOW)
    )
    kinds = {"fusion.1": "a", "fusion.11": "a", "fusion.2": "b", "fusion.99": "c"}
    assert ssm_parts.mixed_seconds(kinds, "unused") == pytest.approx(
        {"": 25.0, "a": 5.0, "b": 1.0}
    )


def test_the_printed_table_lists_every_large_operation(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS})
    spans = [Event("bench.step_call", 0.0, 1.0, "")] * 2
    monkeypatch.setattr(
        tracered, "load", lambda path: Trace({0: OPS}, spans, WINDOW)
    )
    ssm_parts.describe("unused")
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "SSM_PARTS unused steps 2, ms a step"
    rows = {line.split()[0]: line.split()[1:] for line in out[2:] if line[0] != " "}
    assert [float(v) for v in rows["dpwa.ssm.proj"]] == [1000, 1500, 1000, 3500]
    assert [float(v) for v in rows["left"]] == [500, 500, 0, 1000]
    # Folded name and the component after the part's name, each on a line.
    listed = [line.split()[4:] for line in out if line.startswith(" ")]
    assert ["fusion", "in_proj"] in listed and ["fusion", "out_proj"] in listed
    assert ["fusion", "softplus"] in listed and ["copy"] in listed
    assert ["dpwa_selective_scan_bwd", "pallas_call"] in listed
    # Beside a group, what it requires a step at the cell's shapes.
    assert out[-4:-1] == [
        "GROUP ssm_proj 3500.000000 flops required 1.78358e+13 "
        "bytes required 2.42863e+10",
        "GROUP ssm_pointwise 3000.000000 flops required 3.62325e+10 "
        "bytes required 2.31054e+10",
        "GROUP ssm_scan 4000.000000",
    ]
    assert out[-1] == (
        "SUM 11500.000000 of which left 1000.000000; "
        "ssm_scopes' mixer 11500.000000"
    )


def test_the_manifest_appends_the_three_and_changes_no_cell():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index("ssm_proj_ms_per_step")
    assert names[first:first + 3] == list(READERS)
    # The accepted readers of the layer stand before them, as they were.
    for accepted in ("ssm_mixer_ms_per_step", "ssm_scan_ms_per_step",
                     "ssm_scan_roofline"):
        assert names.index(accepted) < first
    assert CELL in [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_jamba_cell(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert m["workloads"][:1] == [CELL]
    assert m["layer"] == "state-space mixer"
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    assert (m["unit"], m["better"]) == READERS[name][1:]
    assert set(m) == {
        "name", "unit", "better", "source", "layer", "moves", "workloads",
    }
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        m["layer"], m["unit"], m["moves"], m["source"]
    )
