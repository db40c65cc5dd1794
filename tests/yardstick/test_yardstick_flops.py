"""The FLOP and byte functions against hand-worked values."""

import pytest
from yardstick_paths import cell_files, load

from benchmark import flops

RESNET = load("benchmark/configs/resnet50.json")
MISTRAL = load("benchmark/configs/mistral-7b-v0.3-lora.json")


def test_resnet50_forward_is_4_1_gmacs():
    # He et al. table 1 gives 3.8e9 for the 50-layer net with the stride on
    # the first 1x1; with it on the 3x3 (torchvision, and models/resnet.py)
    # the usual figure is 4.09e9 multiply-adds.
    macs = flops.resnet_forward_macs(RESNET)
    assert 4.05e9 < macs < 4.15e9
    # The stem alone: 112 * 112 * 7 * 7 * 3 * 64.
    stem_only = dict(RESNET, stage_sizes=[], stage_filters=[])
    assert flops.resnet_forward_macs(stem_only) == (
        112 * 112 * 147 * 64 + 64 * 1000
    )


def test_resnet50_train_flops_per_sample():
    assert flops.resnet_train_flops_per_sample(RESNET) / 1e9 == pytest.approx(
        24.6, abs=0.25
    )


def test_decoder_matmul_params_by_hand():
    p = flops.decoder_matmul_params(MISTRAL)
    # wq, wo 4096x4096; wk, wv 4096x1024; three of 4096x14336.
    assert p["layer"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert p["head"] == 4096 * 32768
    assert flops.lora_params_per_layer(MISTRAL, 16) == 16 * 81920


@pytest.mark.parametrize("seq_len,gflop", [(4096, 2.499), (512, 2.323)])
def test_decoder_flops_per_token_at_two_layers(seq_len, gflop):
    # Base 4 x (2 x 218.1M + 134.2M) = 2.2817; adapters 6 x 2 x 1.31M =
    # 0.0157; attention 6 x T x 4096 x 2 layers = 0.2013 / 0.0252.  (ISSUE 22
    # quotes 2.48 / 2.31, which leaves the adapters out.)
    two = dict(MISTRAL, num_hidden_layers=2)
    got = flops.decoder_lora_train_flops_per_token(two, seq_len, 16) / 1e9
    assert got == pytest.approx(gflop, abs=0.002)


def test_flash_attention_required_by_hand():
    one = dict(MISTRAL, num_hidden_layers=1)
    work = flops.flash_attention_required(one, 4096, sequences=1)
    assert work["flops"] == 6 * 4096 * 4096 * 4096  # 6 matmuls x 2 x T^2 d / 2
    assert work["bytes"] == 12 * 4096 * 4096 * 2


@pytest.mark.parametrize("wire,mb", [("f32", 102.2), ("bf16", 51.1), ("int8", 25.6)])
def test_resnet50_exchange_bytes(wire, mb):
    got = flops.exchange_bytes_per_peer([RESNET["parameters"]], wire) / 1e6
    assert got == pytest.approx(mb, abs=0.06)


def test_lora_exchange_bytes_at_two_layers_and_in_the_cell():
    assert flops.exchange_bytes_per_peer(
        [2 * flops.lora_params_per_layer(MISTRAL, 16)], "f32"
    ) / 1e6 == pytest.approx(10.49, abs=0.01)
    _, config, _ = cell_files("mistral7b-lora-stacked2-t4096")
    layers = config["num_hidden_layers"]
    assert flops.exchange_bytes_per_peer(
        [layers * flops.lora_params_per_layer(config, 16)], "f32"
    ) / 1e6 == pytest.approx(5.243 * layers, abs=0.01)


def test_peaks_know_the_v5e_and_refuse_the_rest():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            flops.peak(kind)
