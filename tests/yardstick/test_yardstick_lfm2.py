"""The LFM2 family's yardstick: the configuration file against the catalog row
and the cut ISSUE 44 states, ``flops_lfm2`` pinned to hand-worked values, what
the builder hands over, the four readers on hand-made events and on the
recorded traces that lack their names, and the manifest's entries.

The lists are held by membership and not by their tails: a later PR appends
a cell or a metric, and none of these tests should be what stops it."""

import glob
import importlib
import json
import os

import pytest
from yardstick_paths import BENCH, MANIFEST, cell_files

from benchmark import block_scopes, flops, flops_lfm2, flops_moe, scopes, tracered
from benchmark.tracered import Event, Trace

CELL = "lfm2-lora-stacked2-t4096"
CONFIG = "lfm2-8b-a1b-lora"
READERS = {  # metric -> (the names it reads, its layer)
    "conv_mixer_ms_per_step": (
        ("dpwa.conv", "dpwa.conv.gate"), "short convolution",
    ),
    "conv_gate_roofline": (("dpwa.conv.gate",), "short convolution"),
    "expert_layer_ms_per_step": (
        ("dpwa.moe.route", "dpwa.moe.experts"), "expert layer",
    ),
    "expert_layer_roofline": (("dpwa.moe.experts",), "expert layer"),
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/"
BWD = (
    STEP + "vmap(transpose(jvp(dpwa.forward)))/Llama/vmap(jvp(dpwa.forward))"
    "/Llama/checkpoint/"
)
AGAIN = BWD + "rematted_computation/"


@pytest.fixture(scope="module")
def files():
    _, config, cell = cell_files(CELL)
    return config, cell


def test_the_file_holds_the_catalog_row_and_the_cut(files):
    config, cell = files
    assert config["family"] == "conv_moe_decoder"
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
    ]
    assert set(config["published"]) == set(config["reduced"])
    assert config["published"]["num_hidden_layers"] == 24
    assert config["published"]["num_dense_layers"] == 2
    published = config["published"]["layer_types"]
    assert len(published) == 24 and published.count("full_attention") == 6
    # The cut is the published layers 1 to 5: one dense layer, one period.
    assert config["layer_types"] == published[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv",
    ]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    for key, value in dict(
        hidden_size=2048, intermediate_size=7168, moe_intermediate_size=1792,
        num_attention_heads=32, num_key_value_heads=8, num_experts=32,
        num_experts_per_tok=4, vocab_size=65536, conv_L_cache=3,
        conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
        use_expert_bias=True, routed_scaling_factor=1, rope_theta=1000000,
        max_position_embeddings=128000, model_type="lfm2_moe",
    ).items():
        assert config[key] == value, key
    assert "pipeline stages" in config["deployment"]
    assert "all 32 experts" in config["deployment"]
    assumed = config["assumed"]
    for key in ("values", "layers_kept", "tie_embedding", "qk_norm",
                "norm_topk_eps", "head_dim", "router", "initial_values",
                "lora", "optimizer", "frozen", "compute_dtype", "base_dtype",
                "remat", "remat_note", "depth_note"):
        assert key in assumed, key
    assert assumed["tie_embedding"] is True and assumed["norm_topk_eps"] == 1e-6
    assert "expert_bias" in assumed["initial_values"]
    assert assumed["lora"]["rank"] == 16 and assumed["remat"] is True
    assert (cell["peers"], cell["per_peer_batch"], cell["seq_len"]) == (2, 1, 4096)
    assert (cell["block_steps"], cell["loss_steps"]) == (4, 8)
    # K is a whole number of blocks (the harness refuses another) and of
    # passes over the pool: its last 8 steps are the fourth pass.
    assert cell["k"] % cell["block_steps"] == 0
    assert cell["k"] % cell["pool_batches"] == 0 and cell["pool_batches"] == 8
    assert cell["warmup_steps"] == 3 and cell["trace_blocks"] == 2
    assert cell["transport"] == "stacked" and cell["schedule"] == "ring"
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    assert cell["exchange_filter"] == "lora" and cell["wire_dtype"] == "f32"
    assert cell["factor"] == 0.5 and cell["overlap"] is False
    assert cell["task"] == dict(
        kind="markov_tokens", successors=4, entropy_nats=1.3863
    )
    # The ceiling is the rule's: single worker + 1.25 x the gap to the gossip
    # median at definition, rounded up to a hundredth.
    said = cell["loss_ceiling_from"]
    single, gossip = (
        said["single_worker_loss_at_k"], said["gossip_loss_at_k_at_definition"]
    )
    by_rule = single + 1.25 * (gossip - single)
    assert by_rule <= cell["loss_ceiling"] < by_rule + 0.01
    assert gossip < cell["loss_ceiling"] < 11.59  # under where the loss starts


def test_every_published_key_equals_the_catalog_rows(files):
    config, _ = files
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = config["published"].get(key, config[key])
        assert want == value, key
    changed = [k for k, v in row["config"].items() if config[k] != v]
    assert sorted(changed) == sorted(config["reduced"])
    # No width among them.
    assert not any(
        k.endswith(("_size", "_dim", "_rank")) or "head" in k for k in changed
    )


def test_layer_kinds_and_value_counts_by_hand(files):
    config, _ = files
    assert flops_lfm2.layer_kinds(config) == dict(
        conv=4, attention=1, dense=1, experts=4
    )
    p = flops_lfm2.parts(config, 16)
    assert p["conv"] == (2048 * 6144 + 2048 * 2048, 16 * (2048 + 6144 + 4096))
    assert p["conv"][0] + 3 * 2048 == 16_783_360
    assert p["attention"] == (
        2 * 2048 * 2048 + 2 * 2048 * 512, 16 * (2 * 4096 + 2 * 2560)
    )
    assert p["attention"][0] + 2 * 64 == 10_485_888
    assert p["dense"] == (3 * 2048 * 7168, 3 * 16 * (2048 + 7168))
    assert p["dense"][0] == 44_040_192
    assert p["expert"] == (11_010_048, 3 * 16 * (2048 + 1792))
    assert p["router"] == (65_536, 0) and p["head"] == (134_217_728, 0)
    # ISSUE 44: 4 x 5,898,240 + 4 x 196,608 + 212,992 + 442,368.
    assert 32 * p["expert"][1] == 5_898_240 and p["conv"][1] == 196_608
    assert p["attention"][1] == 212_992 and p["dense"][1] == 442_368
    assert flops_lfm2.adapter_values(config, 16) == 25_034_752
    # ISSUE 44's replica, and the 4 x 32 bias values beside it.
    assert flops_lfm2.base_values(config) == 1_665_448_064 + 128
    whole = dict(config, **config["published"])
    assert flops_lfm2.base_values(whole) == pytest.approx(8.34e9, rel=1e-3)
    with pytest.raises(ValueError, match="every layer"):
        flops_lfm2.layer_kinds(dict(config, num_hidden_layers=6))


def test_training_flops_per_token_by_hand(files):
    config, _ = files
    frozen = (
        4 * 16_777_216 + 10_485_760 + 44_040_192
        + 4 * (65_536 + 4 * 11_010_048) + 134_217_728
    )
    adapters = 4 * 196_608 + 212_992 + 442_368 + 4 * 4 * 184_320
    core = 3 * 2 * 4096 * 2048  # one attention layer, half the square
    gate = 3 * (2 + 2 * 3) * 2048 * 4
    by_hand = 4 * frozen + 6 * adapters + core + gate
    got = flops_lfm2.lfm2_lora_train_flops_per_token(config, 4096, 16)
    assert got == by_hand == 1_805_975_552
    # 14.79 TFLOP a step of 8,192 tokens (ISSUE 44 wrote 14.4); the head is
    # 31 % of the frozen matmuls at 5 layers and 8 % in the 24-layer model.
    assert got * 8192 == pytest.approx(14.79e12, rel=1e-3)
    assert 134_217_728 / frozen == pytest.approx(0.31, rel=1e-2)
    assert gate / got < 2e-4
    whole = dict(config, **config["published"])
    p = flops_lfm2.parts(whole, 16)
    all_frozen = (
        18 * p["conv"][0] + 6 * p["attention"][0] + 2 * p["dense"][0]
        + 22 * (p["router"][0] + 4 * p["expert"][0]) + p["head"][0]
    )
    assert p["head"][0] / all_frozen == pytest.approx(0.08, abs=0.01)


def test_the_required_work_of_the_new_readers_by_hand(files):
    config, _ = files
    # The gate: 4 conv layers x 8,192 tokens x 2,048 channels; 11 passes of
    # bfloat16, 24 operations a channel.
    gate = flops_lfm2.conv_gate_required(config, 8192)
    assert gate == dict(
        flops=float(24 * 2048 * 8192 * 4), bytes=float(11 * 2 * 2048 * 8192 * 4)
    )
    assert gate["bytes"] / 819e9 == pytest.approx(1.80e-3, rel=1e-2)
    assert gate["bytes"] / 819e9 > gate["flops"] / 197e12  # the bytes bound
    # The experts: ``flops_moe``'s own function at this file's width and
    # its four expert layers.  ISSUE 44: 5.92 TFLOP = 30.0 ms against
    # 20.9 GB = 25.5 ms a step.
    experts = flops_lfm2.expert_layer_required(config, 8192, 2, 16)
    rows, kernel, adapter = 8192 * 4, 11_010_048, 184_320
    assert experts["flops"] == 4 * rows * 2 * (2 * kernel + 3 * adapter)
    assert experts["bytes"] == 4 * (
        64 * (2 * kernel * 2 + 3 * adapter * 4)
        + 3 * rows * 2 * (2048 + 1792) * 3
    )
    assert experts == flops_moe.moe_experts_required(
        dict(config, intermediate_size=1792, num_hidden_layers=4), 8192, 2, 16
    )
    assert experts["flops"] / 197e12 == pytest.approx(30.0e-3, rel=1e-2)
    assert experts["bytes"] / 819e9 == pytest.approx(25.5e-3, rel=1e-2)


def test_the_builder_hands_the_counts_over(files):
    config, cell = files
    builder = importlib.import_module("benchmark.builders.conv_moe_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == 4096 * 1_805_975_552
    assert set(built.kernel_work) == {
        "flash_attention", "expert_layer", "conv_gate",
    }
    # The cores' work at the published head size of 64, one layer, two
    # sequences, whatever the kernels pad it to.
    assert built.kernel_work["flash_attention"] == flops.flash_attention_required(
        dict(config, head_dim=64, num_hidden_layers=1), 4096, 2
    )
    assert built.kernel_work["flash_attention"]["flops"] == 6 * 2 * (
        2 * 4096 * 4096 * 64 * 32 / 2
    )
    assert built.kernel_work["expert_layer"] == flops_lfm2.expert_layer_required(
        config, 8192, 2, 16
    )
    assert built.kernel_work["conv_gate"] == flops_lfm2.conv_gate_required(
        config, 8192
    )
    assert built.batch_shape == dict(vocab_size=65536, seq_len=4096)
    cfg = builder.model_of(config, 4096).cfg
    assert cfg.layer_mixers == ("conv", "attention", "conv", "conv", "conv")
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.d_ff, cfg.d_ff_dense, cfg.n_dense_layers) == (1792, 7168, 1)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.held_experts) == (32, 4, 32)
    assert cfg.router_bias and cfg.qk_norm_per_head and cfg.tie_embeddings
    assert cfg.remat and cfg.conv_taps == 3 and cfg.norm_topk_eps == 1e-6
    assert cfg.param_dtype.__name__ == cfg.dtype.__name__ == "bfloat16"
    import numpy as np

    tokens = np.zeros((2, 1, 4096), np.int32)
    assert built.reference_inputs((tokens[0], tokens[0])).shape == (1, 256)
    toy, toy_cell = builder.rehearse(config, cell)
    assert toy["layer_types"] == config["layer_types"]
    assert toy["moe_intermediate_size"] == 48 and toy_cell["seq_len"] == 64


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "references", "conv_moe_decoder.py")
    with open(path) as f:
        lines = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert lines and not any("dpwa_tpu" in ln or "pallas" in ln for ln in lines)


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


CONV = "layer_0/conv/dpwa.conv/"
MOE = "layer_2/mlp/"
OPS = [
    ev("fusion.1", 0, 2, FWD + CONV + "in_proj/dot_general"),
    ev("fusion.2", 2, 3, FWD + CONV + "dpwa.conv.gate/mul"),
    ev("fusion.3", 3, 4, FWD + "layer_0/dpwa.mlp/mlp/w_gate/dot_general"),
    ev("fusion.4", 4, 5, FWD + MOE + "dpwa.moe.route/top_k"),
    ev("gmm.5", 5, 8, FWD + MOE + "dpwa.moe.experts/pallas_call"),
    ev("fusion.6", 8, 9, FWD + "layer_1/dpwa.attn.gqa/attn/wq/dot_general"),
    ev("gmm.7", 9, 11, AGAIN + MOE + "dpwa.moe.experts/pallas_call"),
    ev("fusion.8", 11, 12, AGAIN + CONV + "dpwa.conv.gate/mul"),
    # The grouped product's hand-written gradient names its own scope.
    ev("gmm.9", 12, 16, BWD + MOE + "dpwa.moe.route/dpwa.moe.experts/pallas_call"),
    ev("fusion.10", 16, 17, BWD + MOE + "dpwa.moe.route/scatter"),
    ev("fusion.11", 17, 19, BWD + CONV + "dpwa.conv.gate/mul"),
    ev("fusion.12", 19, 20, BWD + CONV + "out_proj/transpose"),
    ev("fusion.13", 20, 21, STEP + "dpwa.exchange/mul"),
    ev("gmm.14", 30, 33, FWD + MOE + "dpwa.moe.experts/pallas_call"),  # outside
]
WINDOW = (0.0, 22.0)


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


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    trace = patched(monkeypatch, OPS)
    record = dict(
        traced_steps=2, device_kind="TPU v5 lite",
        kernel_work=dict(
            expert_layer=dict(flops=197e12 * 0.9, bytes=819e9 * 0.1),
            conv_gate=dict(flops=197e12 * 0.01, bytes=819e9 * 0.5),
        ),
    )
    read = {name: r.reduce(trace, record) for name, r in readers().items()}
    # The mixers whole: 2 + 1 + 1 + 2 + 1; their gates 1 + 1 + 2.
    assert read["conv_mixer_ms_per_step"] == pytest.approx(3500.0)
    # The expert layers: route 1 + 1, experts 3 + 2 + 4 (the gradient under
    # both names is booked once).
    assert read["expert_layer_ms_per_step"] == pytest.approx(5500.0)
    # 0.9 s of FLOPs a step (the larger bound) over 4.5 s under the experts'
    # name; 0.5 s of bytes a step (the larger) over 2 s under the gate's.
    assert read["expert_layer_roofline"] == pytest.approx(20.0)
    assert read["conv_gate_roofline"] == pytest.approx(25.0)
    for name, reader in readers().items():
        assert reader.LAYER == READERS[name][1], name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name
    for name in ("expert_layer_roofline", "conv_gate_roofline"):
        assert readers()[name].reduce(
            trace, dict(record, kernel_work=None)
        ) is None


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    """The parent's program on the new cell's step: no convolution scope, so
    the two convolution readers return nothing and do not raise; a program
    with no scope at all gives nothing to any of the four."""
    bare = [e._replace(detail=e.detail.replace("dpwa.conv", "conv")) for e in OPS]
    trace = patched(monkeypatch, bare)
    record = dict(traced_steps=1, device_kind="TPU v5 lite", kernel_work=dict(
        expert_layer=dict(flops=1e12, bytes=1e9),
        conv_gate=dict(flops=1e9, bytes=1e9),
    ))
    for name in ("conv_mixer_ms_per_step", "conv_gate_roofline"):
        assert readers()[name].reduce(trace, record) is None, name
    assert readers()["expert_layer_ms_per_step"].reduce(trace, record) > 0
    monkeypatch.setattr(
        block_scopes, "_of_window", lambda window, root, table: None
    )
    for name, reader in readers().items():
        assert reader.reduce(trace, record) is None, name


@pytest.mark.parametrize("fixture", ["", "scoped"])
@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_traces_that_lack_its_names(
    name, fixture, monkeypatch
):
    """The ResNet steps recorded on a v5e, without scopes and with the four
    phases': neither holds a name these readers look for, so each returns
    None and does not raise."""
    root = os.path.join(BENCH, "fixtures", fixture)
    paths = glob.glob(os.path.join(root, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded trace in " + root)
    (path,) = paths
    monkeypatch.setattr(scopes, "TRACE_ROOT", root)
    block_scopes._of_window.cache_clear()
    record = dict(traced_steps=2, device_kind="TPU v5 lite", kernel_work=dict(
        expert_layer=dict(flops=1e12, bytes=1e9),
        conv_gate=dict(flops=1e9, bytes=1e9),
    ))
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.reduce(tracered.load(path), record) is None
    block_scopes._of_window.cache_clear()


def test_the_names_are_the_programs():
    from dpwa_tpu.utils import scopes as program

    assert tuple(program.CONV) == READERS["conv_mixer_ms_per_step"][0]
    assert program.CONV.gate == "dpwa.conv.gate"
    assert (program.MOE_ROUTE, program.MOE_EXPERTS) == READERS[
        "expert_layer_ms_per_step"
    ][0]
    for name, reader in readers().items():
        (names,) = reader.GROUPS.values()
        assert names == READERS[name][0], name
    # Both convolution names lie under the forward scope's: the accepted
    # table books an op under them to ``other`` (PERF.md section 7 asks a
    # ``benchmark`` PR for the rows).
    op = FWD + CONV + "dpwa.conv.gate/mul"
    assert block_scopes.place_of(op, block_scopes.GROUPS) == ("other", "forward")
    assert "dpwa.conv" not in {
        n for names in block_scopes.GROUPS.values() for n in names
    }


def test_the_manifest_holds_the_configuration_and_the_cell():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL] == dict(
        name=CELL, config=CONFIG, traffic="lora-conv-experts-stacked2-t4096",
        chips=1, why=cells[CELL]["why"],
    )
    # One configuration, one cell of it, and still one four-chip cell.
    assert [w["name"] for w in cells.values() if w["config"] == CONFIG] == [CELL]
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    config = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
    ]
    assert config["file"] == "benchmark/configs/lfm2-8b-a1b-lora.json"
    assert config["source"].startswith("https://huggingface.co/LiquidAI/")
    for entry in (cells[CELL], config):
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    # The accepted cells stand before it, in their order.
    names = list(cells)
    assert names.index(CELL) >= 8 and names[:8] == [
        "resnet50-stacked8-fulltree", "resnet50-ici4-fulltree",
        "mistral7b-lora-stacked2-t4096", "mistral7b-lora-stacked2-t512",
        "olmoe-lora-stacked2-t4096", "axk1-lora-share8-stacked2",
        "jamba2-lora-period14-stacked2", "evabyte-lora-stacked2-t16384",
    ]


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_new_cell(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"] and m["layer"] == READERS[name][1]
    assert not any(w.startswith("resnet50") for w in m["workloads"])
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    assert (m["unit"], m["better"]) == (
        ("%", "higher") if name.endswith("roofline") else ("ms", "lower")
    )


@pytest.mark.parametrize("name, listed", [
    ("attn_ms_per_step", True), ("attn_kernel_ms_per_step", True),
    ("flash_attention_roofline", True),
    # Held to their tails by ``test_yardstick_eva.py``, so not appended
    # (PERF.md section 7 has the rows): the cell's program carries their
    # names all the same.
    ("mlp_ms_per_step", False), ("head_ms_per_step", False),
    ("loss_ms_per_step", False),
    # Held to one cell each by ``test_yardstick_moe.py``.
    ("moe_expert_ms_per_step", False), ("moe_route_ms_per_step", False),
    ("moe_expert_roofline", False),
    # Its table has no convolution row: the mixers would read as ``other``.
    ("model_other_ms_per_step", False),
])
def test_which_accepted_lists_hold_the_new_cell(name, listed):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (CELL in m["workloads"]) == listed
    # Appended: every cell that was there stands before it, in its order.
    if listed:
        before = m["workloads"][:m["workloads"].index(CELL)]
        assert len(before) >= 3 and "mistral7b-lora-stacked2-t4096" in before


def test_every_cell_reports_an_end_to_end_metric_and_a_layer_metric():
    for kind in ("end_to_end", "per_layer"):
        mine = [
            m["name"] for m in MANIFEST[kind]
            if CELL in m.get("workloads", [CELL])
        ]
        assert len(mine) >= 2, kind
    assert {"setup_s", "samples_per_s", "mfu", "peak_hbm_gb", "loss_at_k"} <= {
        m["name"] for m in MANIFEST["end_to_end"]
        if CELL in m.get("workloads", [CELL])
    }
