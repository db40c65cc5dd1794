"""The sparse-expert family's yardstick: ``flops_moe`` pinned to hand-worked
values at the published shapes, the two expert-layer scopes booked on
hand-made events, and the three readers' arithmetic."""

import importlib

import pytest
from yardstick_paths import MANIFEST, cell_files

from benchmark import flops_moe, moe_scopes, scopes
from benchmark.tracered import Event, Trace

CELL = "olmoe-lora-stacked2-t4096"
READERS = ["moe_expert_ms_per_step", "moe_route_ms_per_step",
           "moe_expert_roofline"]
STEP = "jit(_step)/"
FWD = STEP + "vmap(jvp(dpwa.forward))/Llama/layer_0/mlp/"
BWD = STEP + "vmap(transpose(vmap(jvp(dpwa.forward))))/Llama/layer_0/mlp/"


@pytest.fixture(scope="module")
def published():
    _, config, cell = cell_files(CELL)
    return dict(config, num_hidden_layers=16), config, cell


def test_counts_at_the_published_shapes(published):
    full, _, _ = published
    active = flops_moe.active_matmul_params_per_token(full)
    # 8 experts x (2 x 2048 x 1024 + 1024 x 2048)
    assert active["experts"] == 8 * 3 * 2048 * 1024 == 50_331_648
    assert active["attention"] == 4 * 2048 * 2048 == 16_777_216
    assert active["router"] == 2048 * 64 == 131_072
    assert active["head"] == 2048 * 50_304 == 103_022_592
    values = flops_moe.adapter_values_per_layer(full, 16)
    # 4 x 16 x (2048 + 2048); 64 x 3 x 16 x (2048 + 1024)
    assert values["attention"] == 262_144
    assert values["experts"] == 9_437_184
    assert values["layer"] == 9_699_328
    # A token touches an eighth of the expert adapters.
    assert flops_moe.active_adapter_values_per_token(full, 16) == (
        262_144 + 9_437_184 // 8
    )


def test_training_flops_per_token_by_hand(published):
    full, config, cell = published
    t = cell["seq_len"]
    layer = 16_777_216 + 131_072 + 50_331_648
    adapters = 262_144 + 1_179_648
    for layers, cfg in ((16, full), (2, config)):
        by_hand = (
            4 * (layers * layer + 103_022_592)  # frozen: forward, activations
            + 6 * layers * adapters  # adapters: and their own gradients
            + 6 * t * 2048 * layers  # causal attention, 6 matmuls, T / 2
        )
        assert flops_moe.moe_decoder_lora_train_flops_per_token(
            cfg, t, 16
        ) == by_hand
    # Of the frozen matmuls' FLOPs the head is about 40 % at the depth the
    # cell runs and the experts about 40 % (9 % and 69 % in the 16-layer model).
    matmuls = lambda layers: layers * layer + 103_022_592
    assert 0.40 < 103_022_592 / matmuls(2) < 0.45
    assert 0.40 < 2 * 50_331_648 / matmuls(2) < 0.45
    assert 0.08 < 103_022_592 / matmuls(16) < 0.10
    assert 0.65 < 16 * 50_331_648 / matmuls(16) < 0.72


def test_expert_kernel_work_of_the_cell_by_hand(published):
    _, config, cell = published
    tokens = cell["peers"] * cell["per_peer_batch"] * cell["seq_len"]
    assert tokens == 8192
    work = flops_moe.moe_experts_required(config, tokens, cell["peers"], 16)
    rows, kernel, adapter = 65_536, 3 * 2048 * 1024, 3 * 16 * 3072
    assert work["flops"] == 2 * rows * 2 * (2 * kernel + 3 * adapter)
    assert work["flops"] == 3_414_499_000_320  # 3.41 TFLOP: 17.3 ms at peak
    assert work["bytes"] == 2 * (
        2 * 64 * (2 * kernel * 2 + 3 * adapter * 4)  # weights, once a pass
        + 3 * rows * 3 * 3072 * 2  # rows in and out, three passes
    )
    # Bytes bound 16.7 ms, FLOPs 17.3: the two lie within a few per cent.
    assert 0.9 < (work["bytes"] / 819e9) / (work["flops"] / 197e12) < 1.0


def test_the_builder_hands_the_counts_over(published):
    _, config, cell = published
    builder = importlib.import_module("benchmark.builders.moe_decoder")
    built = builder.build(config, cell)
    assert built.flops_per_sample == cell["seq_len"] * (
        flops_moe.moe_decoder_lora_train_flops_per_token(config, 4096, 16)
    )
    assert built.kernel_work["moe_experts"] == flops_moe.moe_experts_required(
        config, 8192, 2, 16
    )
    with pytest.raises(ValueError, match="norm_topk_prob"):
        builder.build(dict(config, norm_topk_prob=True), cell)


def ev(name, start, end, op_name=""):
    return Event(name, float(start), float(end), op_name)


OPS = [
    ev("fusion.1", 0, 1, FWD + "dpwa.moe.route/dot_general"),
    ev("sort.2", 1, 2, FWD + "dpwa.moe.route/sort"),
    ev("ragged-dot-none.3", 2, 6, FWD + "dpwa.moe.experts/ragged_dot_general"),
    ev("fusion.4", 6, 7, FWD + "dpwa.moe.experts/jit(silu)/mul"),
    ev("gather.5", 7, 8, FWD + "dpwa.moe.route/gather"),
    ev("ragged-dot-none.6", 8, 14,
       BWD + "dpwa.moe.experts/dpwa.moe.experts/ragged_dot_general"),
    ev("gather.7", 14, 16, BWD + "dpwa.moe.route/gather"),
    ev("flash_attention.8", 16, 19,
       STEP + "vmap(jvp(dpwa.forward))/Llama/layer_0/attn/pallas_call"),
    ev("copy-done.9", 19, 20),
    ev("fusion.10", 20, 21, STEP + "dpwa.exchange/mul"),
    ev("ragged-dot-none.11", 30, 31, FWD + "dpwa.moe.experts/ragged_dot_general"),
]
WINDOW = (0.0, 24.0)


def test_the_two_scopes_are_booked_forward_and_backward_together():
    seconds = moe_scopes.book(OPS, WINDOW)
    assert seconds == pytest.approx(dict(route=5.0, experts=11.0))
    # Both nest under dpwa.forward: they are part of forward + backward.
    phases = scopes.book(OPS, WINDOW)
    assert phases["forward"] == pytest.approx(11.0)
    assert phases["backward"] == pytest.approx(8.0)
    assert sum(seconds.values()) <= phases["forward"] + phases["backward"]


def test_a_program_without_the_scopes_gives_nothing(monkeypatch):
    bare = [e._replace(detail=e.detail.replace("dpwa.moe.", "moe.")) for e in OPS]
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: bare})
    trace = Trace({0: bare}, [], WINDOW)
    assert moe_scopes.seconds_in("unused", trace) is None
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {})
    assert moe_scopes.seconds_in("unused", trace) is None


def test_the_readers_on_a_small_scoped_trace(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda path: {0: OPS, 1: OPS[:3]})
    trace = Trace({0: OPS, 1: OPS[:3]}, [], WINDOW)
    monkeypatch.setattr(
        moe_scopes, "_of_window",
        lambda window, root: moe_scopes.seconds_in("unused", trace),
    )
    work = dict(flops=197e12 * 2.0, bytes=819e9 * 1.0)  # 2 s at peak a step
    record = dict(traced_steps=2, device_kind="TPU v5 lite",
                  kernel_work=dict(moe_experts=work))
    read = {
        name: importlib.import_module("benchmark.layer_metrics." + name)
        .reduce(trace, record) for name in READERS
    }
    assert read["moe_expert_ms_per_step"] == pytest.approx(5500.0)
    assert read["moe_route_ms_per_step"] == pytest.approx(2500.0)
    assert read["moe_expert_roofline"] == pytest.approx(100 * 2.0 / 5.5)
    for name in READERS:
        reader = importlib.import_module("benchmark.layer_metrics." + name)
        assert reader.reduce(None, record) is None
        assert reader.reduce(trace, dict(record, traced_steps=0)) is None
    roofline = importlib.import_module(
        "benchmark.layer_metrics.moe_expert_roofline"
    )
    assert roofline.reduce(trace, dict(record, kernel_work=None)) is None


def test_the_new_metrics_are_read_in_the_new_cell_only():
    for m in MANIFEST["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["layer"] == "expert layer"
