"""The state-space family's yardstick: the configuration file against the
catalog row, the parameter counts of ISSUE 35 from the built tree's shapes,
``flops_ssm`` pinned to hand-worked values at the published shapes, the
groups of ``ssm_scopes`` booked on hand-made events, and the three readers'
arithmetic."""

import importlib
import json
import os

import pytest
from yardstick_paths import MANIFEST, cell_files

from benchmark import flops, flops_ssm, scopes, ssm_scopes
from benchmark.tracered import Event, Trace

CELL = "jamba2-lora-period14-stacked2"
READERS = ("ssm_mixer_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/layer_1/"
BWD = STEP + "vmap(transpose(vmap(jvp(dpwa.forward))))/jvp(dpwa.forward)/checkpoint/"
AGAIN = BWD + "rematted_computation/"


@pytest.fixture(scope="module")
def files():
    _, config, cell = cell_files(CELL)
    return config, cell


def test_the_file_holds_the_catalog_row_and_the_cut(files):
    config, cell = files
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == dict(num_hidden_layers=28)
    assert config["num_hidden_layers"] == 14 == config["attn_layer_period"]
    for key, value in dict(
        hidden_size=2560, intermediate_size=8192, mamba_d_state=16,
        mamba_dt_rank=160, mamba_d_conv=4, mamba_expand=2,
        num_attention_heads=20, num_key_value_heads=1, vocab_size=65_536,
        attn_layer_offset=7, tie_word_embeddings=True, rms_norm_eps=1e-6,
        num_experts=1, mamba_conv_bias=True, mamba_proj_bias=False,
    ).items():
        assert config[key] == value, key
    assert "pipeline stage" in config["deployment"]
    for key in ("layer_order", "inner_norms", "initial_values", "lora",
                "optimizer", "frozen", "compute_dtype", "base_dtype", "remat"):
        assert key in config["assumed"], key
    assert (cell["peers"], cell["per_peer_batch"], cell["seq_len"]) == (2, 1, 4096)
    assert (cell["block_steps"], cell["k"], cell["loss_steps"]) == (2, 20, 8)
    assert (cell["pool_batches"], cell["warmup_steps"]) == (8, 3)
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    assert cell["exchange_filter"] == "lora" and cell["wire_dtype"] == "f32"


def test_every_published_key_equals_the_catalog_rows(files):
    config, _ = files
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not beside this checkout")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B"
        )
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = config["published"].get(key, config[key])
        assert want == value, key
    changed = [k for k, v in row["config"].items() if config[k] != v]
    assert changed == config["reduced"]


def test_the_parameter_counts_from_the_built_trees_shapes(files):
    """ISSUE 35's arithmetic: a Mamba layer 104,161,472, an attention layer
    76,682,240, the replica cut to one period 1,598,556,096 (the final norm
    among them); 13,938,176 adapter values a peer."""
    import jax

    config, cell = files
    builder = importlib.import_module("benchmark.builders.hybrid_ssm_decoder")
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
    assert base(shapes["layer_0"]["mamba"]) == 41_241_792
    assert base(shapes["layer_0"]) == 104_161_472
    assert base(shapes["layer_7"]["attn"]) == 13_762_560
    assert base(shapes["layer_7"]) == 76_682_240
    assert base(shapes["embed"]) == 167_772_160 and "lm_head" not in shapes
    assert base(shapes) == 13 * 104_161_472 + 76_682_240 + 167_772_160 + 2560
    assert base(shapes) == 1_598_556_096
    assert count(shapes, lambda lora: lora) == 13_938_176
    # Born in bfloat16 but the adapters and each mixer's scan parameters.
    narrow = sum(
        v.size for v in flat(shapes).values() if v.dtype.itemsize == 2
    )
    assert narrow == 1_598_556_096 - 13 * (81_920 + 5120 + 5120)


def test_counts_at_the_published_shapes(files):
    config, _ = files
    p = flops_ssm.parts(config, 16)
    # 2560x10240 + 5120x192 + 160x5120 + 5120x2560
    assert p["mamba"][0] == (
        26_214_400 + 983_040 + 819_200 + 13_107_200
    ) == 41_123_840
    # 2560x2560 twice, 2560x128 twice
    assert p["attention"][0] == 2 * 6_553_600 + 2 * 327_680 == 13_762_560
    assert p["mlp"][0] == 3 * 2560 * 8192 == 62_914_560
    assert p["head"] == (2560 * 65_536, 0)
    assert p["mamba"][1] == 16 * (12_800 + 5312 + 5280 + 7680) == 497_152
    assert p["attention"][1] == 16 * (5120 + 2688 + 2688 + 5120) == 249_856
    assert p["mlp"][1] == 3 * 16 * (2560 + 8192) == 516_096
    assert flops_ssm.layer_kinds(config) == dict(attention=1, mamba=13)
    assert flops_ssm.inner_channels(config) == 5120
    # The adapters of the built tree (the test above): 13,938,176.
    assert 13 * 497_152 + 249_856 + 14 * 516_096 == 13_938_176


def test_training_flops_per_token_by_hand(files):
    config, cell = files
    t = cell["seq_len"]
    frozen = 13 * 41_123_840 + 13_762_560 + 14 * 62_914_560 + 167_772_160
    # ISSUE 35: 3.2 GFLOP a token forward (13 x 208 M + 153 M + 21 M of
    # attention core + 336 M of head).
    forward = 2 * frozen + 2 * t * 2560
    assert 3.19e9 < forward < 3.22e9
    by_hand = (
        4 * frozen + 6 * 13_938_176 + 3 * 2 * t * 2560
        + 3 * 9 * 5120 * 16 * 13
    )
    got = flops_ssm.hybrid_lora_train_flops_per_token(config, t, 16)
    assert got == pytest.approx(by_hand, rel=1e-12)
    # 53.8 TFLOP a step of 8,192 tokens (ISSUE 35's 52.7 and the adapters'
    # 0.7); the scan's own work is 0.4 % of it.
    assert 53.5e12 < got * 8192 < 54.0e12
    assert 0.003 < 27 * 81_920 * 13 / got < 0.006


def test_kernel_work_of_the_cell_by_hand(files):
    config, cell = files
    tokens = cell["peers"] * cell["per_peer_batch"] * cell["seq_len"]
    work = flops_ssm.selective_scan_required(config, tokens)
    assert work["flops"] == 27 * 5120 * 16 * 8192 * 13
    # x, y, dy, dx bfloat16 and delta, ddelta float32 over 5,120 channels;
    # Bm, Cm read twice and their gradients written, bfloat16 over 16 states.
    per_token = 5120 * (2 + 4 + 2) + 64 + 5120 * (2 + 4 + 2 + 2 + 4) + 128
    assert per_token == 112_832
    assert work["bytes"] == per_token * 8192 * 13
    # The bound is bytes, 12 to 1: 14.7 ms a step against 1.2 ms.
    assert work["bytes"] / 819e9 == pytest.approx(14.67e-3, rel=1e-2)
    assert 11 < (work["bytes"] / 819e9) / (work["flops"] / 197e12) < 13
    wide = flops_ssm.selective_scan_required(
        dict(config, assumed=dict(config["assumed"], compute_dtype="float32")),
        tokens,
    )
    assert wide["bytes"] == (5120 * 4 * 8 + 16 * 4 * 6) * 8192 * 13


def test_the_builder_hands_the_counts_over(files):
    config, cell = files
    builder = importlib.import_module("benchmark.builders.hybrid_ssm_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == 4096 * (
        flops_ssm.hybrid_lora_train_flops_per_token(config, 4096, 16)
    )
    # One attention layer of 20 heads of 128, expanded from its one k / v
    # head before the kernels, over two sequences.
    one_layer = dict(config, head_dim=128, num_hidden_layers=1)
    assert built.kernel_work == dict(
        flash_attention=flops.flash_attention_required(one_layer, 4096, 2),
        selective_scan=flops_ssm.selective_scan_required(config, 8192),
    )
    assert built.kernel_work["flash_attention"]["flops"] == (
        6 * 4096 * 4096 * 128 * 20 * 2
    )
    cfg = builder.model_of(config, 4096).cfg
    assert (cfg.attn_layer_period, cfg.attn_layer_offset) == (14, 7)
    assert cfg.rope_theta is None and cfg.tie_embeddings and cfg.remat
    assert cfg.param_dtype.__name__ == cfg.dtype.__name__ == "bfloat16"
    assert cfg.activation_dtype is None and cfg.head_dim == 128
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        builder.build(dict(config, tie_word_embeddings=False), cell)
    toy, toy_cell = builder.rehearse(config, cell)
    assert toy["attn_layer_period"] >= 3 and toy["mamba_d_state"] == 4
    assert toy["mamba_dt_rank"] == 8 and toy_cell["seq_len"] // 128 >= 3
    assert flops_ssm.layer_kinds(toy)["attention"] >= 1


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(
        os.path.dirname(flops_ssm.__file__), "references",
        "hybrid_ssm_decoder.py",
    )
    with open(path) as f:
        lines = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert lines and not any("dpwa_tpu" in ln or "pallas" in ln for ln in lines)


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


OPS = [
    ev("fusion.1", 0, 2, FWD + "mamba/dpwa.ssm/in_proj/dot_general"),
    ev("dpwa_selective_scan_fwd.2", 2, 5,
       FWD + "mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call"),
    ev("fusion.3", 5, 6, FWD + "mlp/w_gate/dot_general"),
    ev("fusion.4", 6, 7, AGAIN + "layer_1/mamba/dpwa.ssm/conv/mul"),
    ev("dpwa_selective_scan_fwd.5", 7, 10,
       AGAIN + "layer_1/mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call"),
    # The hand-written gradient names its own scope and no mixer.
    ev("dpwa_selective_scan_bwd.6", 10, 18,
       BWD + "layer_1/mamba/dpwa.ssm.scan/pallas_call"),
    ev("fusion.7", 18, 19, BWD + "layer_1/mamba/dpwa.ssm.scan/reduce_sum"),
    ev("fusion.8", 19, 21, BWD + "layer_1/mamba/dpwa.ssm/out_proj/transpose"),
    ev("flash_attention.9", 21, 22, FWD + "attn/pallas_call"),
    ev("fusion.10", 22, 23, STEP + "dpwa.exchange/mul"),
    ev("dpwa_selective_scan_fwd.11", 30, 33,
       FWD + "mamba/dpwa.ssm/dpwa.ssm.scan/pallas_call"),
]
WINDOW = (0.0, 24.0)


def test_the_groups_are_booked_forward_backward_and_recomputed_together():
    seconds = ssm_scopes.book(OPS, WINDOW)
    # The scan is part of the mixer: 3 + 3 + 8 + 1 of its 2 + 1 + 2 more.
    assert seconds == pytest.approx(dict(ssm_mixer=20.0, ssm_scan=15.0))
    phases = scopes.book(OPS, WINDOW)
    assert phases["forward"] == pytest.approx(7.0)
    assert phases["backward"] == pytest.approx(15.0)


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    bare = [e._replace(detail=e.detail.replace("dpwa.ssm", "ssm")) for e in OPS]
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: bare})
    trace = Trace({0: bare}, [], WINDOW)
    assert ssm_scopes.seconds_in("unused", trace) is None
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {})
    assert ssm_scopes.seconds_in("unused", trace) is None


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS, 1: OPS[:3]})
    trace = Trace({0: OPS, 1: OPS[:3]}, [], WINDOW)
    monkeypatch.setattr(
        ssm_scopes, "_of_window",
        lambda window, root: ssm_scopes.seconds_in("unused", trace),
    )
    record = dict(
        traced_steps=2, device_kind="TPU v5 lite",
        kernel_work=dict(
            selective_scan=dict(flops=197e12 * 0.1, bytes=819e9 * 1.5)
        ),
    )
    readers = {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        for name in READERS
    }
    read = {name: r.reduce(trace, record) for name, r in readers.items()}
    assert read["ssm_mixer_ms_per_step"] == pytest.approx(10_000.0)
    assert read["ssm_scan_ms_per_step"] == pytest.approx(7500.0)
    # 1.5 s of bytes a step (the larger bound) over 7.5 s of scan a step.
    assert read["ssm_scan_roofline"] == pytest.approx(20.0)
    for name, reader in readers.items():
        assert reader.LAYER == "state-space mixer", name
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name
    assert readers["ssm_scan_roofline"].reduce(
        trace, dict(record, kernel_work=None)
    ) is None
    # A trace of a program without the scopes: nothing, not zero.
    monkeypatch.setattr(ssm_scopes, "_of_window", lambda window, root: None)
    for name, reader in readers.items():
        assert reader.reduce(trace, record) is None, name


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_are_read_in_the_new_cell(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"] and m["layer"] == "state-space mixer"
    assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
    assert m["unit"] == ("%" if name.endswith("roofline") else "ms")


@pytest.mark.parametrize("name", [
    "attn_kernel_ms_per_step", "flash_attention_roofline",
])
def test_the_attention_kernels_metrics_hold_the_new_cell_among_theirs(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"]
    assert "mistral7b-lora-stacked2-t4096" in m["workloads"]
