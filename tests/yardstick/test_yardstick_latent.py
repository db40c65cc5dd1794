"""The latent-attention family's yardstick: ``flops_latent`` pinned to
hand-worked values at the published shapes, the groups of
``latent_scopes`` booked on hand-made events, and the five readers'
arithmetic."""

import importlib

import pytest
from yardstick_paths import MANIFEST, cell_files

from benchmark import flops_latent, latent_scopes, moe_scopes, scopes
from benchmark.tracered import Event, Trace

CELL = "axk1-lora-share8-stacked2"
READERS = {
    "latent_attn_ms_per_step": "latent attention",
    "latent_attn_core_roofline": "latent attention",
    "expert_share_ms_per_step": "expert layer",
    "expert_share_roofline": "expert layer",
    "recompute_ms_per_step": "models",
}
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/layer_1/"
BWD = STEP + "vmap(transpose(vmap(jvp(dpwa.forward))))/jvp(dpwa.forward)/checkpoint/"
AGAIN = BWD + "rematted_computation/"


@pytest.fixture(scope="module")
def files():
    _, config, cell = cell_files(CELL)
    return config, cell


def test_counts_at_the_published_shapes(files):
    config, _ = files
    p = flops_latent.parts(config, 16)
    # 7168x1536 + 1536x12288 + 7168x576 + 512x16384 + 8192x7168
    assert p["attention"][0] == (
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256
    ) == 101_122_048
    assert p["dense"][0] == 3 * 7168 * 18432 == 396_361_728
    assert p["shared"][0] == p["expert"][0] == 3 * 7168 * 2048 == 44_040_192
    # Every held routed expert carries adapters on its three projections.
    assert "held routed expert" in config["assumed"]["lora"]["targets"]
    assert p["expert"][1] == p["shared"][1]
    assert p["router"] == (7168 * 192, 0)  # all 192 columns, frozen
    assert p["head"] == (7168 * 20_480, 0)  # the rows of the vocabulary held
    # rank 16 x (in + out) of each projection
    assert p["attention"][1] == 16 * (
        8704 + 13_824 + 7744 + 16_896 + 15_360
    ) == 1_000_448
    assert p["dense"][1] == 3 * 16 * (7168 + 18_432) == 1_228_800
    assert p["shared"][1] == 3 * 16 * (7168 + 2048) == 442_368
    # 8 of 192 held: a third of an expert a token on average.
    assert flops_latent.held_share(config) == pytest.approx(1 / 24)


def test_an_expert_layers_forward_flops_by_part(files):
    """ISSUE 32's split, at T 1024: 202 + 21 of 343 MFLOP a token forward are
    the latent projections and their core, the shared expert 88, the held
    experts 29; at the cell's T 512 the core is half of that."""
    config, cell = files
    f = flops_latent.forward_flops_per_token(config, 1024, 16)
    assert f["projections"] == 2 * 101_122_048
    assert f["core"] == 1024 * 64 * (192 + 128) == 20_971_520
    assert f["shared"] == 88_080_384 and f["router"] == 2_752_512
    assert f["experts"] == pytest.approx(2 * 44_040_192 / 3)
    assert f["expert_layer"] == pytest.approx(343_408_640.0, rel=1e-9)
    assert 0.64 < (f["projections"] + f["core"]) / f["expert_layer"] < 0.66
    at_cell = flops_latent.forward_flops_per_token(config, cell["seq_len"], 16)
    assert at_cell["core"] == cell["seq_len"] * 64 * 320 == 10_485_760
    assert at_cell["expert_layer"] == pytest.approx(332_922_880.0, rel=1e-9)
    assert f["dense"] == 792_723_456 and f["head"] == 293_601_280


def test_training_flops_per_token_by_hand(files):
    config, cell = files
    t = cell["seq_len"]
    routed = 8 * 8 / 192
    frozen = (
        5 * 101_122_048 + 396_361_728
        + 4 * (44_040_192 + 1_376_256 + routed * 44_040_192) + 146_800_640
    )
    # the attention's, the dense layer's, the shared expert's, and of the
    # held routed experts' a third of one a token
    adapters = 5 * 1_000_448 + 1_228_800 + 4 * (1 + routed) * 442_368
    by_hand = 4 * frozen + 6 * adapters + 3 * 5 * t * 64 * 320
    got = flops_latent.latent_moe_lora_train_flops_per_token(config, t, 16)
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert 5.3e9 < got < 5.7e9  # 5.4 GFLOP of frozen matmuls, and the rest
    # The recomputed forward is not in it: twice the forward plus the
    # adapters' own gradients and the core's backward, nothing a third time.
    forward = frozen * 2 + adapters * 2 + 5 * t * 64 * 320
    assert got < 2.2 * forward


def test_kernel_work_of_the_cell_by_hand(files):
    config, cell = files
    sequences = cell["peers"] * cell["per_peer_batch"]
    t = cell["seq_len"]
    core = flops_latent.latent_core_required(config, t, sequences)
    assert t == 512
    assert core["flops"] == 3 * t * t * 64 * 320 * 5 * 2 == 161_061_273_600
    assert core["bytes"] == 6 * t * 64 * 320 * 2 * 5 * 2 == 1_258_291_200
    # 0.82 ms of FLOPs against 1.54 ms of bytes at T 512: the bound is bytes
    # (at T 1024 the two meet: 3.27 against 3.07 ms).
    assert 0.5 < (core["flops"] / 197e12) / (core["bytes"] / 819e9) < 0.6
    at_1024 = flops_latent.latent_core_required(config, 1024, sequences)
    assert 1.0 < (at_1024["flops"] / 197e12) / (at_1024["bytes"] / 819e9) < 1.1
    held = flops_latent.held_experts_required(config, sequences * t, 2, 16)
    rows = 1024 * 8 * 8 / 192
    assert held["flops"] == pytest.approx(
        4 * rows * 2 * (2 * 44_040_192 + 3 * 442_368)
    )
    # The frozen kernels once a pass, two passes; the adapters (float32)
    # three; each projection's rows in and out, three passes.
    assert held["bytes"] == pytest.approx(4 * (
        16 * (2 * 44_040_192 * 2 + 3 * 442_368 * 4) + 3 * rows * 27_648 * 2
    ))
    # 16 groups of about 21 rows: reading the weights is the bound, 11 to 1.
    assert 10 < (held["bytes"] / 819e9) / (held["flops"] / 197e12) < 13
    assert held["bytes"] / 819e9 == pytest.approx(14.46e-3, rel=1e-3)


def test_the_builder_hands_the_counts_over(files):
    config, cell = files
    builder = importlib.import_module("benchmark.builders.latent_moe_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == 512 * (
        flops_latent.latent_moe_lora_train_flops_per_token(config, 512, 16)
    )
    assert built.kernel_work == dict(
        latent_attn_core=flops_latent.latent_core_required(config, 512, 2),
        held_experts=flops_latent.held_experts_required(config, 1024, 2, 16),
    )
    cfg = builder.model_of(config, 512).cfg
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset) == (192, 8, 0)
    assert cfg.remat and cfg.param_dtype.__name__ == "bfloat16"
    assert cfg.activation_dtype.__name__ == "float32"
    assert cfg.dtype.__name__ == "bfloat16"
    with pytest.raises(ValueError, match="topk_method"):
        builder.build(dict(config, topk_method="noaux_tc"), cell)


def test_the_file_holds_the_catalog_row_and_the_cut(files):
    config, cell = files
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]
    assert config["published"] == dict(
        num_hidden_layers=61, n_routed_experts=192, vocab_size=163_840
    )
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 20_480)
    # The guide's floors: four layers after the dense one, 8 experts, 1/8.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for key, value in dict(
        hidden_size=7168, intermediate_size=18_432, moe_intermediate_size=2048,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=64,
        num_experts_per_tok=8, n_shared_experts=1, routed_scaling_factor=2.5,
        scoring_func="sigmoid", norm_topk_prob=True, n_group=8, topk_group=4,
    ).items():
        assert config[key] == value, key
    assert "24 chips" in config["deployment"]
    assert (cell["peers"], cell["per_peer_batch"], cell["k"]) == (2, 1, 30)
    assert cell["expect_hlo"] == ["tpu_custom_call"]


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


OPS = [
    ev("fusion.1", 0, 2, FWD + "attn/dpwa.attn.latent/wq_a/dot_general"),
    ev("flash_attention.2", 2, 3, FWD + "attn/dpwa.attn.latent/pallas_call"),
    ev("fusion.3", 3, 4, FWD + "mlp/dpwa.moe.route/dot_general"),
    ev("gmm.4", 4, 6, FWD + "mlp/dpwa.moe.experts/pallas_call"),
    ev("fusion.5", 6, 7, FWD + "mlp/dpwa.moe.shared/shared/w_gate/dot_general"),
    ev("fusion.6", 7, 9, AGAIN + "layer_1/attn/dpwa.attn.latent/wq_a/dot_general"),
    ev("flash_attention.7", 9, 10, AGAIN + "layer_1/attn/dpwa.attn.latent/pallas_call"),
    ev("gmm.8", 10, 12, AGAIN + "layer_1/mlp/dpwa.moe.experts/pallas_call"),
    ev("flash_mha_bwd_dq_x.9", 12, 14, BWD + "layer_1/attn/dpwa.attn.latent/pallas_call"),
    ev("gmm.10", 14, 17,
       BWD + "layer_1/mlp/dpwa.moe.experts/dpwa.moe.experts/pallas_call"),
    ev("fusion.11", 17, 18, BWD + "layer_1/mlp/dpwa.moe.shared/transpose"),
    ev("copy-done.12", 18, 19),
    ev("fusion.13", 19, 20, STEP + "dpwa.exchange/mul"),
    ev("gmm.14", 30, 31, FWD + "mlp/dpwa.moe.experts/pallas_call"),
]
WINDOW = (0.0, 24.0)


def test_the_groups_are_booked_forward_backward_and_recomputed_together():
    seconds = latent_scopes.book(OPS, WINDOW)
    assert seconds == pytest.approx(dict(
        latent_attn=8.0, expert_share=10.0, recompute=5.0,
    ))
    # The routed experts' two scopes, part of it, are the accepted module's.
    assert moe_scopes.book(OPS, WINDOW) == pytest.approx(
        dict(route=1.0, experts=7.0)
    )
    # The recomputation is part of what the phases book as backward.
    phases = scopes.book(OPS, WINDOW)
    assert phases["forward"] == pytest.approx(7.0)
    assert phases["backward"] == pytest.approx(11.0)
    assert seconds["recompute"] <= phases["backward"]


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    bare = [
        e._replace(detail=e.detail.replace("dpwa.attn.latent", "attn")
                   .replace("dpwa.moe.", "moe.")
                   .replace("rematted_computation/", ""))
        for e in OPS
    ]
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: bare})
    trace = Trace({0: bare}, [], WINDOW)
    assert latent_scopes.seconds_in("unused", trace) is None
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {})
    assert latent_scopes.seconds_in("unused", trace) is None


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS, 1: OPS[:3]})
    trace = Trace({0: OPS, 1: OPS[:3]}, [], WINDOW)
    monkeypatch.setattr(
        latent_scopes, "_of_window",
        lambda window, root: latent_scopes.seconds_in("unused", trace),
    )
    monkeypatch.setattr(
        moe_scopes, "_of_window",
        lambda window, root: moe_scopes.seconds_in("unused", trace),
    )
    at_peak = lambda s: dict(flops=197e12 * s, bytes=819e9 * s / 2)
    record = dict(
        traced_steps=2, device_kind="TPU v5 lite",
        kernel_work=dict(latent_attn_core=at_peak(0.5), held_experts=at_peak(1.0)),
    )
    readers = {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        for name in READERS
    }
    read = {name: r.reduce(trace, record) for name, r in readers.items()}
    assert read["latent_attn_ms_per_step"] == pytest.approx(4000.0)
    assert read["expert_share_ms_per_step"] == pytest.approx(5000.0)
    assert read["recompute_ms_per_step"] == pytest.approx(2500.0)
    # 0.5 s of core work a step over the three attention kernels' 4 s in two
    # steps, the recomputed forward among them.
    assert read["latent_attn_core_roofline"] == pytest.approx(100 * 0.5 / 2.0)
    assert read["expert_share_roofline"] == pytest.approx(100 * 1.0 / 3.5)
    for name, reader in readers.items():
        assert reader.reduce(None, record) is None, name
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None, name
    for name in ("latent_attn_core_roofline", "expert_share_roofline"):
        assert readers[name].reduce(trace, dict(record, kernel_work=None)) is None
    # A trace of a program without latent attention: nothing, not zero.
    monkeypatch.setattr(latent_scopes, "_of_window", lambda window, root: None)
    monkeypatch.setattr(moe_scopes, "_of_window", lambda window, root: None)
    for name in READERS:
        if name != "latent_attn_core_roofline":
            assert readers[name].reduce(trace, record) is None, name


def test_the_new_metrics_are_read_in_the_new_cell_only():
    found = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in READERS}
    assert set(found) == set(READERS)
    for name, m in found.items():
        assert m["workloads"] == [CELL] and m["layer"] == READERS[name]
        assert m["moves"] == "samples_per_s" and m["source"] == "device_trace"
