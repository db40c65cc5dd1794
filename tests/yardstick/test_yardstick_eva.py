"""The EVA family's yardstick: the configuration file against the catalog row,
the parameter counts of ISSUE 42 from the built tree's shapes, ``flops_eva``
pinned to hand-worked values at T 4,096 and T 16,384, the four readers on
hand-made events and on the recorded traces that lack their names, and the
manifest's eighth cell."""

import glob
import importlib
import json
import os

import pytest
from yardstick_paths import BENCH, MANIFEST, cell_files

from benchmark import block_scopes, flops_eva, scopes, tracered
from benchmark.tracered import Event, Trace

CELL = "evabyte-lora-stacked2-t16384"
READERS = {  # metric -> the names it reads
    "eva_attn_ms_per_step": (
        "dpwa.attn.eva", "dpwa.attn.eva.summaries", "dpwa.attn.eva.core",
    ),
    "eva_core_ms_per_step": ("dpwa.attn.eva.core",),
    "eva_summaries_ms_per_step": ("dpwa.attn.eva.summaries",),
    "eva_core_roofline": ("dpwa.attn.eva.core",),
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/layer_1/"
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
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == dict(num_hidden_layers=32)
    assert config["num_hidden_layers"] == 4
    for key, value in dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, vocab_size=320, window_size=2048,
        chunk_size=16, num_pred_heads=8, rope_theta=100000,
        rms_norm_eps=1e-5, attention_class="eva", norm_add_unit_offset=True,
        fp32_skip_add=True, fp32_ln=False, fp32_logits=True, mixedp_attn=True,
        tie_word_embeddings=False, max_position_embeddings=32768,
    ).items():
        assert config[key] == value, key
    assert "pipeline stages" in config["deployment"]
    for key in ("chunk_weights", "remote_set", "pred_heads", "qk_norm",
                "initial_values", "float32", "lora", "optimizer", "frozen",
                "compute_dtype", "base_dtype", "remat", "depth_note"):
        assert key in config["assumed"], key
    assert "fp32_skip_add" in config["assumed"]["float32"]
    assert (cell["peers"], cell["per_peer_batch"], cell["seq_len"]) == (2, 1, 16384)
    assert (cell["block_steps"], cell["k"], cell["loss_steps"]) == (1, 16, 8)
    assert (cell["pool_batches"], cell["warmup_steps"]) == (8, 3)
    assert cell["trace_blocks"] == 2 and cell["schedule"] == "ring"
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    assert cell["exchange_filter"] == "lora" and cell["wire_dtype"] == "f32"
    assert cell["task"] == dict(
        kind="markov_tokens", successors=4, entropy_nats=1.3863
    )


def test_every_published_key_equals_the_catalog_rows(files):
    config, _ = files
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = config["published"].get(key, config[key])
        assert want == value, key
    changed = [k for k, v in row["config"].items() if config[k] != v]
    assert changed == config["reduced"]


def test_the_parameter_counts_from_the_built_trees_shapes(files):
    """ISSUE 42's arithmetic: a layer 202,391,552 (four 4096^2 projections,
    three 4096 x 11,008, ``phi``, ``mu`` and two norms), the replica cut to
    four layers 821,366,784 with the embedding, the 2,560-column head and
    the final norm; 4,997,120 adapter values a peer."""
    import jax

    config, cell = files
    builder = importlib.import_module("benchmark.builders.eva_decoder")
    built = builder.build(config, cell)
    shapes = jax.eval_shape(built.init_fn, jax.random.key(0))["params"]
    flat = lambda tree: {
        jax.tree_util.keystr(p): v
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }
    count = lambda tree, keep: sum(
        v.size for k, v in flat(tree).items() if keep("lora_" in k)
    )
    base = lambda tree: count(tree, lambda lora: not lora)
    assert base(shapes["layer_0"]["attn"]) == 4 * 4096 ** 2 + 2 * 32 * 128
    assert base(shapes["layer_0"]["mlp"]) == 3 * 4096 * 11008 == 135_266_304
    assert base(shapes["layer_0"]) == 202_391_552
    assert base(shapes["embed"]) == 1_310_720
    assert base(shapes["lm_head"]) == 4096 * 2560 == 10_485_760
    assert base(shapes) == 4 * 202_391_552 + 1_310_720 + 10_485_760 + 4096
    assert base(shapes) == 821_366_784
    assert count(shapes["layer_0"], lambda lora: lora) == 1_249_280
    assert count(shapes, lambda lora: lora) == 4_997_120
    # Born in bfloat16 but the adapters: 1.64 GB a replica, 20 MB exchanged.
    narrow = sum(v.size for v in flat(shapes).values() if v.dtype.itemsize == 2)
    assert narrow == 821_366_784 and 2 * narrow == pytest.approx(1.64e9, rel=2e-3)
    assert 4 * 4_997_120 == pytest.approx(20.0e6, rel=1e-3)


def test_score_entries_by_hand(files):
    config, _ = files
    # T 4,096: two windows' lower triangles with their diagonals, and the
    # second window's 2,048 queries against the first's 128 summaries.
    assert flops_eva.score_entries(config, 4096) == dict(
        local=2 * (2048 * 2049 // 2), remote=2048 * 128,
    )
    assert 2048 * 2049 // 2 == 2_098_176
    # T 16,384: eight windows; window w sees 128 w summaries, w = 0..7.
    got = flops_eva.score_entries(config, 16384)
    assert got == dict(local=16_785_408, remote=7_340_032)
    assert got["remote"] == sum(2048 * 128 * w for w in range(8))
    # A query sees 1,024.5 exact keys and 448 summaries on average.
    assert got["local"] / 16384 == 1024.5 and got["remote"] / 16384 == 448
    with pytest.raises(ValueError, match="whole number of windows"):
        flops_eva.score_entries(config, 5000)


def test_counts_at_the_published_shapes(files):
    config, _ = files
    p = flops_eva.parts(config, 16)
    assert p["attention"] == (4 * 4096 ** 2, 4 * 16 * 8192)
    assert p["mlp"] == (3 * 4096 * 11008, 3 * 16 * (4096 + 11008))
    assert p["head"] == (4096 * 320 * 8, 0)
    assert p["attention"][1] + p["mlp"][1] == 1_249_280
    # The head is 1.3 % of the frozen matmuls at 4 layers and 0.16 % at 32
    # (ISSUE 42 wrote 0.3 % and 0.04 %: a quarter of its own 10,485,760).
    a_layer = p["attention"][0] + p["mlp"][0]
    assert p["head"][0] / (4 * a_layer + p["head"][0]) == pytest.approx(
        0.0128, rel=1e-2
    )
    assert p["head"][0] / (32 * a_layer + p["head"][0]) == pytest.approx(
        0.0016, rel=2e-2
    )


@pytest.mark.parametrize("steps, entries", [
    (4096, 4_196_352 + 262_144), (16384, 16_785_408 + 7_340_032),
])
def test_training_flops_per_token_by_hand(files, steps, entries):
    config, _ = files
    frozen = 4 * (67_108_864 + 135_266_304) + 10_485_760
    assert frozen == 819_986_432
    # QK^T and PV, a multiply-add two operations, over 32 heads of 128.
    core_forward = 2 * 2 * 4096 * entries
    assert flops_eva.core_forward_flops(config, steps) == core_forward
    by_hand = (
        4 * frozen + 6 * 4_997_120
        + 4 * (3.5 * core_forward / steps + 3 * 6 * 4096)
    )
    got = flops_eva.eva_lora_train_flops_per_token(config, steps, 16)
    assert got == pytest.approx(by_hand, rel=1e-12)
    if steps == 16384:
        assert got == 3_647_979_520
        # 119.5 TFLOP a step of 32,768 bytes; the core is 9.3 % of it.
        assert got * 32768 == pytest.approx(119.5e12, rel=1e-3)
        assert 4 * 3.5 * core_forward / steps / got == pytest.approx(
            0.0926, rel=1e-2
        )


@pytest.mark.parametrize("steps, sequences, flops, bytes_", [
    # 3.5 x forward x 4 layers x sequences; 12 tensors and 6 summaries of
    # T x 4096 bfloat16 a layer a sequence.
    (4096, 1, 3.5 * 16384 * 4_458_496 * 4, (12 * 33_554_432 + 6 * 2_097_152) * 4),
    (16384, 2, 3.5 * 16384 * 24_125_440 * 8, (12 * 134_217_728 + 6 * 8_388_608) * 8),
])
def test_the_cores_required_work_by_hand(files, steps, sequences, flops, bytes_):
    config, _ = files
    work = flops_eva.eva_core_required(config, steps, sequences)
    assert work == dict(flops=flops, bytes=float(bytes_))
    if steps == 16384:
        # ISSUE 42: 11.1 TFLOP a step, 56 ms at the chip's peak; the bound
        # is FLOPs, 3.5 to 1 over 13.3 GB at 819 GB/s.
        assert work["flops"] == pytest.approx(11.07e12, rel=1e-3)
        assert work["flops"] / 197e12 == pytest.approx(56.2e-3, rel=1e-2)
        assert work["bytes"] / 819e9 == pytest.approx(16.2e-3, rel=1e-2)


def test_the_builder_hands_the_counts_over(files):
    config, cell = files
    builder = importlib.import_module("benchmark.builders.eva_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == 16384 * 3_647_979_520
    assert built.kernel_work == dict(
        eva_attention=flops_eva.eva_core_required(config, 16384, 2)
    )
    assert built.batch_shape == dict(vocab_size=320, seq_len=16384)
    cfg = builder.model_of(config, 16384).cfg
    assert (cfg.eva_window, cfg.eva_chunk, cfg.n_pred_heads) == (2048, 16, 8)
    assert (cfg.n_heads, cfg.head_dim, cfg.d_ff) == (32, 128, 11008)
    assert cfg.rope_theta == 100000 and cfg.remat and cfg.norm_unit_offset
    assert cfg.fp32_skip_add and cfg.activation_dtype is None
    assert cfg.param_dtype.__name__ == cfg.dtype.__name__ == "bfloat16"
    # The model check reads one sequence's first three windows.
    import numpy as np

    tokens = np.zeros((2, 1, 16384), np.int32)
    assert built.reference_inputs((tokens[0], tokens[0])).shape == (1, 6144)
    toy, toy_cell = builder.rehearse(config, cell)
    assert toy_cell["seq_len"] == 3 * toy["window_size"]
    assert toy["vocab_size"] == 320 and toy["num_pred_heads"] == 3


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "references", "eva_decoder.py")
    with open(path) as f:
        lines = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert lines and not any("dpwa_tpu" in ln or "pallas" in ln for ln in lines)


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


EVA = "dpwa.attn.eva/attn/"
OPS = [
    ev("fusion.1", 0, 2, FWD + EVA + "wq/dot_general"),
    ev("fusion.2", 2, 3,
       FWD + EVA + "checkpoint/dpwa.attn.eva.summaries/reduce_sum"),
    ev("dpwa_eva_attention_fwd.3", 3, 6,
       FWD + EVA + "dpwa.attn.eva.core/pallas_call"),
    ev("fusion.4", 6, 7, FWD + "dpwa.mlp/mlp/w_gate/dot_general"),
    ev("dpwa_eva_attention_fwd.5", 7, 10,
       AGAIN + "layer_1/" + EVA + "dpwa.attn.eva.core/pallas_call"),
    # The hand-written gradient names the core's scope and no mixer.
    ev("dpwa_eva_attention_bwd.6", 10, 18,
       BWD + "layer_1/dpwa.attn.eva.core/pallas_call"),
    ev("fusion.7", 18, 19, BWD + "layer_1/dpwa.attn.eva.core/reduce_sum"),
    ev("fusion.8", 19, 21,
       BWD + "layer_1/" + EVA + "rematted_computation/dpwa.attn.eva.summaries/mul"),
    ev("fusion.9", 21, 22, BWD + "layer_1/" + EVA + "wo/transpose"),
    ev("fusion.10", 22, 23, STEP + "dpwa.exchange/mul"),
    ev("dpwa_eva_attention_fwd.11", 30, 33,
       FWD + EVA + "dpwa.attn.eva.core/pallas_call"),  # outside the window
]
WINDOW = (0.0, 24.0)


def readers():
    return {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        for name in READERS
    }


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS, 1: OPS[:3]})
    trace = Trace({0: OPS, 1: OPS[:3]}, [], WINDOW)
    monkeypatch.setattr(
        block_scopes, "_of_window",
        lambda window, root, table: block_scopes.seconds_in(
            "unused", dict(table), trace
        ),
    )
    record = dict(
        traced_steps=2, device_kind="TPU v5 lite",
        kernel_work=dict(
            eva_attention=dict(flops=197e12 * 1.5, bytes=819e9 * 0.1)
        ),
    )
    read = {name: r.reduce(trace, record) for name, r in readers().items()}
    # The mixer whole: 2 + 1 + 3 + 3 + 8 + 1 + 2 + 1 of the 22 s under the
    # forward scope; the core 3 + 3 + 8 + 1; the summaries 1 + 2.
    assert read["eva_attn_ms_per_step"] == pytest.approx(10_500.0)
    assert read["eva_core_ms_per_step"] == pytest.approx(7500.0)
    assert read["eva_summaries_ms_per_step"] == pytest.approx(1500.0)
    # 1.5 s of FLOPs a step (the larger bound) over 7.5 s of core a step.
    assert read["eva_core_roofline"] == pytest.approx(20.0)
    for name, reader in readers().items():
        assert reader.LAYER == "EVA attention", name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name
    assert readers()["eva_core_roofline"].reduce(
        trace, dict(record, kernel_work=None)
    ) is None


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    """The parent's program: the same events with no EVA name in them."""
    bare = [
        e._replace(detail=e.detail.replace("dpwa.attn.eva", "attn.eva"))
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
    record = dict(traced_steps=1, device_kind="TPU v5 lite", kernel_work=dict(
        eva_attention=dict(flops=1e12, bytes=1e9)
    ))
    for name, reader in readers().items():
        assert reader.reduce(trace, record) is None, name
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
    phases': neither holds an EVA name, so each reader returns None and does
    not raise."""
    root = os.path.join(BENCH, "fixtures", fixture)
    paths = glob.glob(os.path.join(root, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded trace in " + root)
    (path,) = paths
    monkeypatch.setattr(scopes, "TRACE_ROOT", root)
    block_scopes._of_window.cache_clear()
    record = dict(traced_steps=2, device_kind="TPU v5 lite", kernel_work=dict(
        eva_attention=dict(flops=1e12, bytes=1e9)
    ))
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.reduce(tracered.load(path), record) is None
    block_scopes._of_window.cache_clear()


def test_the_names_are_the_programs():
    from dpwa_tpu.utils import scopes as program

    assert tuple(program.ATTN_EVA) == READERS["eva_attn_ms_per_step"]
    assert program.ATTN_EVA.core == "dpwa.attn.eva.core"
    assert program.ATTN_EVA.summaries == "dpwa.attn.eva.summaries"
    for name, reader in readers().items():
        if name != "eva_core_roofline":
            (names,) = reader.GROUPS.values()
            assert names == READERS[name], name
    # Every name lies under the forward scope's: an op under it is booked
    # to forward / backward and, by the accepted table, to ``other``.
    op = FWD + EVA + "dpwa.attn.eva.core/pallas_call"
    assert block_scopes.place_of(op, block_scopes.GROUPS) == ("other", "forward")


def test_the_manifest_takes_the_eighth_cell():
    cells = MANIFEST["workloads"]
    assert len(cells) == 8 and cells[-1]["name"] == CELL
    assert cells[-1] == dict(
        name=CELL, config="evabyte-6.5b-lora",
        traffic="lora-eva-stacked2-t16384", chips=1, why=cells[-1]["why"],
    )
    assert sum(w["chips"] == 4 for w in cells) == 1
    config = MANIFEST["configs"][-1]
    assert config["name"] == "evabyte-6.5b-lora"
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/evabyte-6.5b-lora.json"
    for entry in (cells[-1], config):
        assert 1 <= len(entry["why"]) <= 200, entry["name"]


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_new_cell(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["layer"] == "EVA attention"
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    assert (m["unit"], m["better"]) == (
        ("%", "higher") if name.endswith("roofline") else ("ms", "lower")
    )
    assert [m["name"] for m in MANIFEST["per_layer"]][-4:] == list(READERS)


@pytest.mark.parametrize("name, listed", [
    ("mlp_ms_per_step", True), ("head_ms_per_step", True),
    ("loss_ms_per_step", True), ("model_other_ms_per_step", False),
    ("attn_ms_per_step", False), ("attn_kernel_ms_per_step", False),
    ("flash_attention_roofline", False), ("recompute_ms_per_step", False),
])
def test_which_accepted_lists_hold_the_new_cell(name, listed):
    """At the tail of the three lists whose names the cell's program
    carries; in none whose reader would book the EVA mixer as something
    else (the accepted table's remainder, the library's flash kernels)."""
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (m["workloads"][-1] == CELL) == listed
    assert (CELL in m["workloads"]) == listed
