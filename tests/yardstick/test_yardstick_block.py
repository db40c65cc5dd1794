"""The decoder block's yardstick: ``block_scopes``' one table held to the
program's names, its booking function on hand-made events (groups by pass, the
partition of forward + backward, the prefix trap, nesting, the shared expert),
the five readers' arithmetic, and each new entry's cells."""

import importlib

import pytest
from yardstick_paths import MANIFEST

from benchmark import block_scopes, scopes
from benchmark.tracered import Event, Trace

T4096, T512, OLMOE, AXK1, JAMBA = (
    "mistral7b-lora-stacked2-t4096", "mistral7b-lora-stacked2-t512",
    "olmoe-lora-stacked2-t4096", "axk1-lora-share8-stacked2",
    "jamba2-lora-period14-stacked2",
)
# metric -> (the group it reads, its layer, the cells ISSUE 39 lists it in).
READERS = {
    "attn_ms_per_step": ("attn_gqa", "attention", [T4096, T512, OLMOE, JAMBA]),
    "mlp_ms_per_step": ("mlp", "models", [T4096, T512, JAMBA, AXK1]),
    "head_ms_per_step": ("head", "models", [T4096, T512, OLMOE, AXK1, JAMBA]),
    "loss_ms_per_step": ("loss", "models", [OLMOE, AXK1, JAMBA]),
    "model_other_ms_per_step": (
        "other", "models", [T4096, T512, OLMOE, AXK1, JAMBA],
    ),
}
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/"
BWD = STEP + "vmap(transpose(jvp(dpwa.forward)))/Llama/vmap(jvp(dpwa.forward))/Llama/checkpoint/"
AGAIN = BWD + "rematted_computation/"


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


OPS = [
    ev("fusion.1", 0, 1, FWD + "embed/take"),
    ev("fusion.2", 1, 3, FWD + "layer_0/dpwa.attn.gqa/attn/wq/dot_general"),
    ev("flash_attention.3", 3, 4,
       FWD + "layer_0/dpwa.attn.gqa/attn/jit(flash_attention)/pallas_call"),
    ev("fusion.4", 4, 5, FWD + "layer_0/mlp_norm/mul"),
    ev("fusion.5", 5, 8, FWD + "layer_0/dpwa.mlp/mlp/w_gate/dot_general"),
    ev("fusion.6", 8, 9, FWD + "layer_1/attn/dpwa.attn.latent/wq_a/dot_general"),
    ev("fusion.7", 9, 10, FWD + "layer_1/mlp/dpwa.moe.route/dot_general"),
    ev("gmm.8", 10, 12, FWD + "layer_1/mlp/dpwa.moe.experts/pallas_call"),
    # The shared expert is an MLP module under the expert layer's name.
    ev("fusion.9", 12, 13,
       FWD + "layer_1/mlp/dpwa.moe.shared/shared/w_gate/dot_general"),
    ev("fusion.10", 13, 14, FWD + "layer_2/mamba/dpwa.ssm/in_proj/dot_general"),
    ev("dpwa_selective_scan_fwd.11", 14, 16,
       FWD + "layer_2/mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call"),
    ev("fusion.12", 16, 18, FWD + "dpwa.head/lm_head/dot_general"),
    ev("fusion.13", 18, 19, FWD[:-len("Llama/")] + "dpwa.loss/reduce_max"),
    ev("fusion.14", 19, 21, AGAIN + "layer_0/dpwa.mlp/mlp/w_up/dot_general"),
    ev("fusion.15", 21, 22, AGAIN + "layer_0/attn_norm/mul"),
    # The scan's hand-written gradient names its own scope and no mixer.
    ev("dpwa_selective_scan_bwd.16", 22, 25,
       BWD + "layer_2/mamba/dpwa.ssm.scan/pallas_call"),
    ev("fusion.17", 25, 27, BWD + "layer_0/dpwa.mlp/mlp/w_down/transpose"),
    # A route instruction traced inside the experts' name: the outermost.
    ev("fusion.18", 27, 28,
       BWD + "layer_1/mlp/dpwa.moe.experts/dpwa.moe.route/gather"),
    ev("fusion.19", 28, 29, BWD + "layer_0/add"),
    ev("fusion.20", 29, 30, STEP + "dpwa.optimizer/mul"),
    ev("copy.21", 30, 31, ""),
    ev("fusion.22", 40, 43, FWD + "dpwa.head/lm_head/dot_general"),
]
WINDOW = (0.0, 32.0)
BOOKED = {
    "attn_gqa": (3, 0, 0), "attn_latent": (1, 0, 0), "mlp": (3, 2, 2),
    "moe_route": (1, 0, 0), "moe_experts": (2, 1, 0), "moe_shared": (1, 0, 0),
    "ssm": (3, 3, 0), "head": (2, 0, 0), "loss": (1, 0, 0),
    "other": (2, 1, 1),
}


def test_the_table_holds_the_programs_names():
    from dpwa_tpu.utils import scopes as program

    assert block_scopes.GROUPS == {
        "attn_gqa": (program.ATTN_GQA,), "attn_latent": (program.ATTN_LATENT,),
        "mlp": (program.MLP,), "moe_route": (program.MOE_ROUTE,),
        "moe_experts": (program.MOE_EXPERTS,),
        "moe_shared": (program.MOE_SHARED,),
        "ssm": (program.SSM, program.SSM_SCAN), "head": (program.HEAD,),
        "loss": (program.LOSS,),
    }
    # Every name the program puts inside the forward scope is in the table.
    inside = {
        value for key, value in vars(program).items()
        if key.isupper() and isinstance(value, str)
    } - {program.FORWARD, program.OPTIMIZER, program.EXCHANGE}
    assert inside == {n for names in block_scopes.GROUPS.values() for n in names}


def test_the_groups_are_booked_by_pass():
    seconds = block_scopes.book(OPS, WINDOW)
    assert list(seconds) == [*block_scopes.GROUPS, "other"]
    for group, (forward, backward, recomputed) in BOOKED.items():
        assert seconds[group] == pytest.approx(dict(
            forward=forward, backward=backward, recomputed=recomputed
        )), group


def test_groups_and_other_partition_forward_and_backward():
    seconds = block_scopes.book(OPS, WINDOW)
    phases = scopes.book(OPS, WINDOW)
    total = sum(sum(by_pass.values()) for by_pass in seconds.values())
    assert total == phases["forward"] + phases["backward"] == 29.0
    assert sum(s["forward"] for s in seconds.values()) == phases["forward"]
    assert phases["optimizer"] == 1.0 and phases["unscoped"] == 1.0


@pytest.mark.parametrize("op_name, place", [
    (FWD + "layer_1/attn/dpwa.attn.latent/wq_a/dot_general",
     ("attn_latent", "forward")),
    (FWD + "layer_0/dpwa.attn.gqa/attn/wq/dot_general", ("attn_gqa", "forward")),
    # A name is a whole component: no prefix, no longer name, no module path.
    (FWD + "layer_0/dpwa.attn/attn/wq/dot_general", ("other", "forward")),
    (FWD + "layer_0/dpwa.attn.gqa2/attn/wq/dot_general", ("other", "forward")),
    (FWD + "layer_0/dpwa.mlp.x/mlp/w_up/dot_general", ("other", "forward")),
    (FWD + "layer_0/mlp/w_up/dot_general", ("other", "forward")),
    (FWD + "layer_0/attn/wq/dot_general", ("other", "forward")),
    # Nested names: once, to the outermost.
    (FWD + "layer_2/mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call",
     ("ssm", "forward")),
    (BWD + "layer_2/mamba/dpwa.ssm.scan/pallas_call", ("ssm", "backward")),
    (BWD + "layer_1/mlp/dpwa.moe.route/dpwa.moe.experts/mul",
     ("moe_route", "backward")),
    # The shared expert's MLP module carries the expert layer's name alone.
    (FWD + "layer_1/mlp/dpwa.moe.shared/shared/w_gate/dot_general",
     ("moe_shared", "forward")),
    # JAX wraps a name when a transformation is traced under it.
    (BWD + "layer_1/mlp/vmap(dpwa.moe.experts)/pallas_call",
     ("moe_experts", "backward")),
    (AGAIN + "layer_0/dpwa.attn.gqa/attn/wk/dot_general",
     ("attn_gqa", "recomputed")),
    # Of names the compiler joined, the first is read.
    (FWD + "dpwa.head/dot_general;" + FWD + "dpwa.loss/exp", ("head", "forward")),
    (STEP + "dpwa.optimizer/dpwa.mlp/mul", None),
    (STEP + "dpwa.exchange/gather", None),
    ("", None),
])
def test_a_name_is_a_whole_component_and_the_outermost_wins(op_name, place):
    assert block_scopes.place_of(op_name, block_scopes.GROUPS) == place


def test_the_booking_takes_any_table():
    table = {"mixer_norms": ("dt_norm", "b_norm"), "scan": ("dpwa.ssm.scan",)}
    ops = [
        ev("fusion.1", 0, 2, FWD + "layer_2/mamba/dpwa.ssm/dt_norm/mul"),
        ev("k.2", 2, 5, FWD + "layer_2/mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call"),
        ev("fusion.3", 5, 6, FWD + "layer_2/mamba/dpwa.ssm/in_proj/dot_general"),
    ]
    seconds = block_scopes.book(ops, (0.0, 6.0), table)
    assert {g: s["forward"] for g, s in seconds.items()} == dict(
        mixer_norms=2.0, scan=3.0, other=1.0
    )


def readers():
    return {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        for name in READERS
    }


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS, 1: OPS[:5]})
    trace = Trace({0: OPS, 1: OPS[:5]}, [], WINDOW)
    monkeypatch.setattr(
        block_scopes, "_of_window",
        lambda window, root, table: block_scopes.seconds_in(
            "unused", dict(table), trace
        ),
    )
    record = dict(traced_steps=2)
    for name, reader in readers().items():
        group, layer, _ = READERS[name]
        assert reader.reduce(trace, record) == pytest.approx(
            1e3 * sum(BOOKED[group]) / 2
        ), name
        assert reader.LAYER == layer, name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(traced_steps=0)) is None, name


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    # The parent's program: dpwa.forward and no name below it.  Every named
    # group reads nothing (not zero); what is left is all of it.
    bare = [
        e._replace(detail=e.detail.replace("dpwa.", "x.").replace(
            "x.forward", "dpwa.forward"
        ))
        for e in OPS
    ]
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: bare})
    trace = Trace({0: bare}, [], WINDOW)
    monkeypatch.setattr(
        block_scopes, "_of_window",
        lambda window, root, table: block_scopes.seconds_in(
            "unused", dict(table), trace
        ),
    )
    record = dict(traced_steps=1)
    for name, reader in readers().items():
        value = reader.reduce(trace, record)
        if name == "model_other_ms_per_step":
            assert value == pytest.approx(29_000.0)
        else:
            assert value is None, name
    # A program without any scope, and a trace with no file: nothing at all.
    none = [e._replace(detail="") for e in OPS]
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: none})
    assert block_scopes.seconds_in("unused", trace=trace) is None
    for replaced in (
        lambda window, root, table: block_scopes.seconds_in(
            "unused", dict(table), trace
        ),
        lambda window, root, table: None,
    ):
        monkeypatch.setattr(block_scopes, "_of_window", replaced)
        for name, reader in readers().items():
            assert reader.reduce(trace, record) is None, name


def test_one_reading_of_the_file_serves_the_five_readers(monkeypatch, tmp_path):
    (tmp_path / "run").mkdir()
    (tmp_path / "run" / "t.xplane.pb").write_bytes(b"")
    trace = Trace({0: OPS}, [], WINDOW)
    read = []
    monkeypatch.setattr(block_scopes.tracered, "load", lambda path: trace)
    monkeypatch.setattr(
        scopes, "scoped_ops", lambda path: read.append(path) or {0: OPS}
    )
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    block_scopes._of_window.cache_clear()
    values = [
        reader.reduce(trace, dict(traced_steps=1))
        for reader in readers().values()
    ]
    block_scopes._of_window.cache_clear()
    assert all(value for value in values) and len(read) == 1


def test_describe_prints_a_table_that_closes(monkeypatch, capsys):
    spans = [ev("bench.step_call", 0, 1), ev("bench.block_sync", 1, 32)]
    trace = Trace({0: OPS}, spans, WINDOW)
    monkeypatch.setattr(block_scopes.tracered, "load", lambda path: trace)
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS})
    block_scopes.describe("unused")
    out = capsys.readouterr().out.splitlines()
    assert out[0].endswith("steps 1, ms a step")
    row = next(line for line in out if line.startswith("mlp "))
    assert row.split() == ["mlp", "3000.000", "2000.000", "2000.000", "7000.000"]
    # Under a group, its operations by the event's own name, largest first.
    assert out[out.index(row) + 1].split() == ["7000.000", "fusion"]
    assert out[-1] == "SUM 29000.000000 forward + backward 29000.000000"


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_cells_the_issue_lists(name):
    _, layer, cells = READERS[name]
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (m["layer"], m["unit"], m["better"]) == (layer, "ms", "lower")
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    # The head of the list, not ``==``: a later PR may append a cell.
    assert m["workloads"][:len(cells)] == cells
    assert not any(cell.startswith("resnet50") for cell in m["workloads"])


def test_the_accepted_groups_are_not_doubled():
    # The expert layer, latent attention and the mixer keep their own
    # metrics; none of the new ones reads their names.
    groups = {group for group, _, _ in READERS.values()}
    assert groups == {"attn_gqa", "mlp", "head", "loss", "other"}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    for accepted in (
        "latent_attn_ms_per_step", "expert_share_ms_per_step",
        "moe_expert_ms_per_step", "moe_route_ms_per_step",
        "ssm_mixer_ms_per_step", "ssm_scan_ms_per_step",
    ):
        assert names.count(accepted) == 1
