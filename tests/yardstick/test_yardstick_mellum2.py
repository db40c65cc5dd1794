"""The Mellum 2 family's yardstick: the configuration file against the catalog
row and the cut ISSUE 49 states, ``flops_window`` pinned to hand-worked values,
what the builder hands over, the three readers on hand-made events and on the
recorded traces that lack their names, and the manifest's entries.

The lists are held by membership and not by their tails: a later PR appends
a cell or a metric, and none of these tests should be what stops it."""

import glob
import importlib
import json
import os

import pytest
from yardstick_paths import BENCH, MANIFEST, cell_files

from benchmark import block_scopes, flops_moe, flops_window, scopes, tracered
from benchmark.tracered import Event, Trace

CELL = "mellum2-lora-stacked2-t4096"
CONFIG = "mellum2-12b-a2.5b-lora"
READERS = {  # metric -> its layer
    "window_attn_ms_per_step": "attention",
    "window_kernel_ms_per_step": "attention kernels",
    "window_kernel_roofline": "attention kernels",
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/"
BWD = (
    STEP + "vmap(transpose(jvp(dpwa.forward)))/Llama/vmap(jvp(dpwa.forward))"
    "/Llama/checkpoint/"
)
AGAIN = BWD + "rematted_computation/"
KINDS = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def files():
    _, config, cell = cell_files(CELL)
    return config, cell


def test_the_file_holds_the_catalog_row_and_the_cut(files):
    config, cell = files
    assert config["family"] == "window_moe_decoder"
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "vocab_size",
    ]
    assert set(config["published"]) == set(config["reduced"])
    published = config["published"]
    assert published["num_hidden_layers"] == 28
    assert published["vocab_size"] == 98304
    assert published["layer_types"] == 7 * KINDS
    assert published["mlp_layer_types"] == 28 * ["sparse"]
    # The cut is the published layers 0 to 3, one whole period, and a
    # quarter of the vocabulary (the floor is an eighth).
    assert config["layer_types"] == published["layer_types"][:4] == KINDS
    assert config["mlp_layer_types"] == 4 * ["sparse"]
    assert config["num_hidden_layers"] == 4
    assert config["vocab_size"] == 24576 == 98304 // 4 >= 98304 // 8
    for key, value in dict(
        hidden_size=2304, head_dim=128, intermediate_size=7168,
        moe_intermediate_size=896, num_attention_heads=32,
        num_key_value_heads=4, num_experts=64, num_experts_per_tok=8,
        sliding_window=1024, rms_norm_eps=1e-6, norm_topk_prob=True,
        attention_bias=False, hidden_act="silu", tie_word_embeddings=False,
        use_sliding_window=True, max_window_layers=0,
        max_position_embeddings=131072, model_type="mellum",
    ).items():
        assert config[key] == value, key
    assert config["rope_parameters"] == dict(
        full_attention=dict(
            rope_type="yarn", rope_theta=500000, factor=16,
            original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
            attention_factor=1.2772588722239782,
        ),
        sliding_attention=dict(rope_type="default", rope_theta=500000),
    )
    assert "pipeline stages" in config["deployment"]
    assert "all 64 experts" in config["deployment"]
    assert "all 32 heads" in config["deployment"]
    assert "a quarter here" in config["deployment"]
    assumed = config["assumed"]
    for key in ("values", "layers_kept", "vocabulary", "qk_norm", "window",
                "rope", "mtp_head", "unused_keys", "router", "initial_values",
                "lora", "optimizer", "frozen", "compute_dtype", "base_dtype",
                "remat", "remat_note"):
        assert key in assumed, key
    assert "the query's own among them" in assumed["window"]
    assert "not built" in assumed["mtp_head"]
    assert "intermediate_size" in assumed["unused_keys"]
    assert assumed["lora"]["rank"] == 16 and assumed["lora"]["alpha"] == 16.0
    assert assumed["optimizer"] == dict(name="adam", learning_rate=0.001)
    assert assumed["compute_dtype"] == assumed["base_dtype"] == "bfloat16"
    assert assumed["remat"] is True and "14.0" in assumed["remat_note"]
    assert (cell["peers"], cell["per_peer_batch"], cell["seq_len"]) == (2, 1, 4096)
    assert (cell["block_steps"], cell["loss_steps"], cell["k"]) == (4, 8, 32)
    # K is a whole number of blocks (the harness refuses another) and of
    # passes over the pool: its last 8 steps are the fourth pass.
    assert cell["k"] % cell["block_steps"] == 0
    assert cell["k"] % cell["pool_batches"] == 0 and cell["pool_batches"] == 8
    assert cell["warmup_steps"] == 3 and cell["trace_blocks"] == 2
    assert cell["transport"] == "stacked" and cell["schedule"] == "ring"
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    assert cell["exchange_filter"] == "lora" and cell["wire_dtype"] == "f32"
    assert cell["factor"] == 0.5 and cell["overlap"] is False
    assert cell["fetch_probability"] == 1.0
    assert cell["task"] == dict(
        kind="markov_tokens", successors=4, entropy_nats=1.3863
    )
    # The rule (single worker + 1.25 x the gap to the gossip median) leaves
    # no room where the gap is a hundredth of a nat: the ceiling is 2 % over
    # the gossip median at definition, rounded up, as the ici cell's is.
    said = cell["loss_ceiling_from"]
    single, gossip = (
        said["single_worker_loss_at_k"], said["gossip_loss_at_k_at_definition"]
    )
    assert single + 1.25 * (gossip - single) < gossip + 0.02
    assert 1.02 * gossip <= cell["loss_ceiling"] < 1.02 * gossip + 0.01
    assert gossip < cell["loss_ceiling"] < 10.61  # under where the loss starts


def test_every_published_key_equals_the_catalog_rows(files):
    config, _ = files
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "Mellum2-12B-A2.5B-Instruct"
        )
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = config["published"].get(key, config[key])
        assert want == value, key
    changed = [k for k, v in row["config"].items() if config[k] != v]
    assert sorted(changed) == sorted(config["reduced"])
    # No width among them: the vocabulary's rows are the chip's share.
    assert not any(
        k.endswith(("_dim", "_rank")) or "head" in k or "window" in k
        or k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                 "num_experts", "num_experts_per_tok")
        for k in changed
    )


def test_pairs_and_value_counts_by_hand(files):
    config, _ = files
    assert flops_window.layer_kinds(config) == dict(
        sliding_attention=3, full_attention=1
    )
    # ISSUE 49: 3,670,528 of 8,390,656 pairs a head a sequence, 43.7 %.
    assert flops_window.pairs(4096) == 4096 * 4097 // 2 == 8_390_656
    assert flops_window.pairs(4096, 1024) == 3_670_528 == (
        1024 * 1025 // 2 + 3072 * 1024
    )
    assert flops_window.pairs(4096, 1024) == sum(
        min(t + 1, 1024) for t in range(4096)
    )
    assert flops_window.pairs(4096, 1024) / flops_window.pairs(4096) == (
        pytest.approx(0.437, abs=5e-4)
    )
    assert flops_window.pairs(8192, 1024) / flops_window.pairs(8192) == (
        pytest.approx(0.234, abs=5e-4)
    )
    assert flops_window.pairs(512, 1024) == flops_window.pairs(512)
    assert flops_window.core_pairs_per_sequence(config, 4096) == (
        3 * 3_670_528 + 8_390_656
    )
    p = flops_window.parts(config, 16)
    assert p["attention"] == (
        2 * 2304 * 4096 + 2 * 2304 * 512,
        16 * (2 * (2304 + 4096) + 2 * (2304 + 512)),
    )
    assert p["attention"][0] + 2 * 128 == 21_233_920
    assert p["expert"] == (6_193_152, 3 * 16 * 3200)
    assert p["router"] == (147_456, 0) and p["head"] == (2304 * 24576, 0)
    assert flops_window.layer_values(config) == 417_747_712
    assert flops_window.base_values(config) == 1_784_239_360
    assert flops_window.adapter_values(config, 16) == 4 * 10_125_312 == 40_501_248
    whole = dict(config, **config["published"])
    assert flops_window.base_values(whole) == pytest.approx(12.15e9, rel=1e-3)
    with pytest.raises(ValueError, match="every layer"):
        flops_window.layer_kinds(dict(config, num_hidden_layers=5))
    with pytest.raises(ValueError, match="sparse"):
        flops_window.layer_kinds(dict(config, mlp_layer_types=4 * ["dense"]))


def test_training_flops_per_token_by_hand(files):
    config, _ = files
    frozen = 4 * (21_233_664 + 147_456 + 8 * 6_193_152) + 2304 * 24576
    adapters = 4 * (294_912 + 8 * 153_600)
    # Six matmuls a pair over the band in three layers and the triangle in
    # one, 4,096 columns a position, a token's share.
    core = 6 * 2 * 4096 * (3 * 3_670_528 + 8_390_656) / 4096
    by_hand = 4 * frozen + 6 * adapters + core
    got = flops_window.window_lora_train_flops_per_token(config, 4096, 16)
    assert got == by_hand
    # 13.36 TFLOP a step of 8,192 tokens; the cores are 14 % of it with the
    # window and would be 22 % without; the head 17 % of the frozen matmuls
    # at 4 layers, a quarter of the vocabulary (10 % in the 28-layer model).
    assert got * 8192 == pytest.approx(13.36e12, rel=1e-3)
    assert core / got == pytest.approx(0.143, abs=5e-4)
    full = 6 * 2 * 4096 * 4 * 8_390_656 / 4096
    assert full / (got - core + full) == pytest.approx(0.224, abs=5e-4)
    assert 2304 * 24576 / frozen == pytest.approx(0.166, abs=5e-4)
    whole = 28 * (21_233_664 + 147_456 + 8 * 6_193_152) + 2304 * 98304
    assert 2304 * 98304 / whole == pytest.approx(0.10, abs=5e-3)


def test_the_required_work_of_the_readers_by_hand(files):
    config, _ = files
    # The band: three layers x two sequences x 32 heads of 128, seven
    # matmuls a pair; bytes: six tensors at 32 heads and six at 4.
    window = flops_window.window_core_required(config, 4096, 2)
    assert window["flops"] == 7 * 2 * 128 * 32 * 3 * 3_670_528 * 2
    assert window["bytes"] == (
        6 * 4096 * 32 * 128 * 2 + 6 * 4096 * 4 * 128 * 2
    ) * 3 * 2
    assert window["pairs"] == 3 * 3_670_528
    assert window["triangle_pairs"] == 3 * 8_390_656
    # 1.26 TFLOP = 6.4 ms against 1.36 GB = 1.7 ms a step: the FLOPs bound.
    assert window["flops"] / 197e12 == pytest.approx(6.41e-3, rel=1e-2)
    assert window["bytes"] / 819e9 == pytest.approx(1.66e-3, rel=1e-2)
    # Every layer's core by the accepted count (six matmuls a pair).
    both = flops_window.attention_required(config, 4096, 2)
    assert both["flops"] == 6 * 2 * 128 * 32 * (3 * 3_670_528 + 8_390_656) * 2
    assert both["bytes"] == window["bytes"] * 4 / 3
    # The experts: ``flops_moe``'s own function at this file's width.
    experts = flops_window.expert_layer_required(config, 8192, 2, 16)
    rows, kernel, adapter = 8192 * 8, 6_193_152, 153_600
    assert experts["flops"] == 4 * rows * 2 * (2 * kernel + 3 * adapter)
    assert experts["bytes"] == 4 * (
        128 * (2 * kernel * 2 + 3 * adapter * 4)
        + 3 * rows * 2 * (2304 + 896) * 3
    )
    assert experts == flops_moe.moe_experts_required(
        dict(config, intermediate_size=896), 8192, 2, 16
    )
    # 6.74 TFLOP = 34.2 ms against 28.5 GB = 34.8 ms: the two bounds meet.
    assert experts["flops"] / 197e12 == pytest.approx(34.2e-3, rel=1e-2)
    assert experts["bytes"] / 819e9 == pytest.approx(34.8e-3, rel=1e-2)


def test_the_builder_hands_the_counts_over(files):
    config, cell = files
    builder = importlib.import_module("benchmark.builders.window_moe_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == 4096 * (
        flops_window.window_lora_train_flops_per_token(config, 4096, 16)
    )
    assert set(built.kernel_work) == {
        "flash_attention", "window_attention", "expert_layer",
    }
    assert built.kernel_work["flash_attention"] == (
        flops_window.attention_required(config, 4096, 2)
    )
    assert built.kernel_work["window_attention"] == (
        flops_window.window_core_required(config, 4096, 2)
    )
    assert built.kernel_work["expert_layer"] == (
        flops_window.expert_layer_required(config, 8192, 2, 16)
    )
    assert built.batch_shape == dict(vocab_size=24576, seq_len=4096)
    cfg = builder.model_of(config, 4096).cfg
    assert cfg.layer_mixers == tuple(KINDS) and cfg.sliding_window == 1024
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.d_model, cfg.d_ff, cfg.n_dense_layers) == (2304, 896, 0)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.held_experts) == (64, 8, 64)
    assert cfg.qk_norm_per_head and cfg.norm_topk_prob and not cfg.tie_embeddings
    assert cfg.remat and cfg.norm_eps == 1e-6 and not cfg.router_bias
    assert cfg.param_dtype.__name__ == cfg.dtype.__name__ == "bfloat16"
    assert dict((kind, theta) for kind, theta, _ in cfg.rope_by_kind) == dict(
        sliding_attention=500000.0, full_attention=500000.0
    )
    import numpy as np

    tokens = np.zeros((2, 1, 4096), np.int32)
    # Two windows of one sequence: half of the queries have a whole window
    # behind them.
    assert built.reference_inputs((tokens[0], tokens[0])).shape == (1, 2048)
    toy, toy_cell = builder.rehearse(config, cell)
    assert toy["layer_types"] == config["layer_types"]
    assert toy["sliding_window"] * 4 == toy_cell["seq_len"] == 512
    assert toy["head_dim"] * toy["num_attention_heads"] != toy["hidden_size"]


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "references", "window_moe_decoder.py")
    with open(path) as f:
        lines = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert lines and not any("dpwa_tpu" in ln or "pallas" in ln for ln in lines)


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


SLIDING = "layer_0/dpwa.attn.gqa/dpwa.attn.window/attn/"
FULL = "layer_3/dpwa.attn.gqa/attn/"
OPS = [
    ev("fusion.1", 0, 2, FWD + SLIDING + "wq/dot_general"),
    ev("flash_attention_fwd_dpwa_window.2", 2, 3, FWD + SLIDING + "pallas_call"),
    ev("fusion.3", 3, 4, FWD + FULL + "wq/dot_general"),
    ev("flash_attention_fwd_dpwa.4", 4, 6, FWD + FULL + "pallas_call"),
    ev("fusion.5", 6, 7, FWD + "layer_0/mlp/dpwa.moe.route/top_k"),
    ev("flash_attention_fwd_dpwa_window.6", 7, 8, AGAIN + SLIDING + "pallas_call"),
    ev("flash_mha_bwd_dpwa_window.7", 8, 11, BWD + SLIDING + "pallas_call"),
    ev("fusion.8", 11, 12, BWD + SLIDING + "wo/transpose"),
    ev("flash_mha_bwd_dpwa.9", 12, 17, BWD + FULL + "pallas_call"),
    ev("fusion.10", 17, 18, STEP + "dpwa.exchange/mul"),
    ev("flash_mha_bwd_dpwa_window.11", 30, 33, BWD + SLIDING + "pallas_call"),
]
WINDOW = (0.0, 20.0)  # the last event lies outside


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
            window_attention=dict(flops=197e12 * 0.5, bytes=819e9 * 0.1),
            flash_attention=dict(flops=197e12 * 1.2, bytes=819e9 * 0.2),
        ),
    )
    read = {name: r.reduce(trace, record) for name, r in readers().items()}
    # The sliding layer whole: 2 + 1 + 1 + 3 + 1 over two steps.
    assert read["window_attn_ms_per_step"] == pytest.approx(4000.0)
    # Its kernels by their names: 1 + 1 + 3; the full layer's are not among.
    assert read["window_kernel_ms_per_step"] == pytest.approx(2500.0)
    # 0.5 s of FLOPs a step (the larger bound) over 2.5 s of kernels.
    assert read["window_kernel_roofline"] == pytest.approx(20.0)
    # The accepted readers count both forms: 1 + 2 + 1 + 3 + 5.
    accepted = lambda name: importlib.import_module(
        "benchmark.layer_metrics." + name
    ).reduce(trace, record)
    assert accepted("attn_kernel_ms_per_step") == pytest.approx(6000.0)
    assert accepted("flash_attention_roofline") == pytest.approx(20.0)
    # ``dpwa.attn.window`` lies inside ``dpwa.attn.gqa`` and is booked to it.
    assert accepted("attn_ms_per_step") == pytest.approx(8000.0)
    for name, reader in readers().items():
        assert reader.LAYER == READERS[name], name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name
    assert readers()["window_kernel_roofline"].reduce(
        trace, dict(record, kernel_work=None)
    ) is None


def test_a_program_without_the_window_gives_nothing(monkeypatch):
    """A program that has neither the name nor the kernels (the parent's, on
    any cell): the three readers return nothing and do not raise."""
    bare = [
        e._replace(
            name=e.name.replace("_dpwa_window", "_dpwa"),
            detail=e.detail.replace("dpwa.attn.window/", ""),
        ) for e in OPS
    ]
    trace = patched(monkeypatch, bare)
    record = dict(traced_steps=1, device_kind="TPU v5 lite", kernel_work=dict(
        window_attention=dict(flops=1e12, bytes=1e9),
    ))
    for name, reader in readers().items():
        assert reader.reduce(trace, record) is None, name
    monkeypatch.setattr(
        block_scopes, "_of_window", lambda window, root, table: None
    )
    assert readers()["window_attn_ms_per_step"].reduce(trace, record) is None


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
        window_attention=dict(flops=1e12, bytes=1e9),
    ))
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.reduce(tracered.load(path), record) is None
    block_scopes._of_window.cache_clear()


def test_the_names_are_the_programs():
    from dpwa_tpu.ops import eva
    from dpwa_tpu.utils import scopes as program

    reader = readers()["window_attn_ms_per_step"]
    assert tuple(reader.GROUPS.values()) == (tuple(program.ATTN_WINDOW),)
    assert program.ATTN_WINDOW.whole == "dpwa.attn.window"
    # The accepted table books an op under both names to attention, the
    # outermost: ``model_other_ms_per_step`` holds nothing of a sliding layer.
    op = FWD + SLIDING + "wq/dot_general"
    assert block_scopes.place_of(op, block_scopes.GROUPS) == (
        "attn_gqa", "forward"
    )
    assert "dpwa.attn.window" not in {
        n for names in block_scopes.GROUPS.values() for n in names
    }
    import re

    pattern = readers()["window_kernel_ms_per_step"].WINDOW_KERNEL
    assert all(re.search(pattern, name) for name in eva.BAND_KERNEL_NAMES)


def test_the_manifest_holds_the_configuration_and_the_cell():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL] == dict(
        name=CELL, config=CONFIG, traffic="lora-window-experts-stacked2-t4096",
        chips=1, why=cells[CELL]["why"],
    )
    # One configuration, one cell of it, and still one four-chip cell.
    assert [w["name"] for w in cells.values() if w["config"] == CONFIG] == [CELL]
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    config = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "vocab_size",
    ]
    assert config["file"] == "benchmark/configs/mellum2-12b-a2.5b-lora.json"
    assert config["source"].startswith("https://huggingface.co/JetBrains/")
    for entry in (cells[CELL], config):
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    # The accepted cells stand before it, in their order.
    names = list(cells)
    assert names.index(CELL) >= 9 and names[:9] == [
        "resnet50-stacked8-fulltree", "resnet50-ici4-fulltree",
        "mistral7b-lora-stacked2-t4096", "mistral7b-lora-stacked2-t512",
        "olmoe-lora-stacked2-t4096", "axk1-lora-share8-stacked2",
        "jamba2-lora-period14-stacked2", "evabyte-lora-stacked2-t16384",
        "lfm2-lora-stacked2-t4096",
    ]


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_new_cell(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"] and m["layer"] == READERS[name]
    assert not any(w.startswith("resnet50") for w in m["workloads"])
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    assert (m["unit"], m["better"]) == (
        ("%", "higher") if name.endswith("roofline") else ("ms", "lower")
    )


@pytest.mark.parametrize("name, listed", [
    ("attn_ms_per_step", True), ("attn_kernel_ms_per_step", True),
    ("flash_attention_roofline", True), ("expert_layer_ms_per_step", True),
    ("expert_layer_roofline", True), ("model_other_ms_per_step", True),
    # Held to their tails by ``test_yardstick_eva.py``, so not appended
    # (PERF.md section 7 has the rows): the cell's program carries their
    # names all the same.
    ("head_ms_per_step", False), ("loss_ms_per_step", False),
    # Held to one cell each by ``test_yardstick_moe.py`` and
    # ``test_yardstick_latent.py``.
    ("moe_expert_ms_per_step", False), ("moe_route_ms_per_step", False),
    ("moe_expert_roofline", False), ("recompute_ms_per_step", False),
    # No dense feed-forward in this model.
    ("mlp_ms_per_step", False),
])
def test_which_accepted_lists_hold_the_new_cell(name, listed):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (CELL in m["workloads"]) == listed
    # Appended: every cell that was there stands before it, in its order.
    if listed:
        before = m["workloads"][:m["workloads"].index(CELL)]
        assert before and set(before) <= {
            w["name"] for w in MANIFEST["workloads"]
        } - {CELL}


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
