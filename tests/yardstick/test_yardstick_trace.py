"""The reduction from a trace to numbers: interval arithmetic on hand-made
events, and the whole path on a trace recorded on a v5e."""

import glob
import json
import os

import pytest
from yardstick_paths import BENCH

from benchmark import tracered
from benchmark.tracered import Event, Trace


def ev(name, start, end):
    return Event(name, float(start), float(end), "")


def test_fold_strips_numeric_suffixes():
    assert tracered.fold("%fusion.123") == "fusion"
    assert tracered.fold("collective-permute-start.2.1") == "collective-permute-start"
    assert tracered.fold("convolution") == "convolution"
    assert tracered.fold("flash_mha_bwd_dq_block_k_128.11") == "flash_mha_bwd_dq_block_k_128"


def test_union_merges_and_clips():
    got = tracered.union([(0, 2), (1, 3), (5, 6), (9, 12)], lo=0.5, hi=10)
    assert got == [[0.5, 3], [5, 6], [9, 10]]


def test_busy_idle_and_gap_attribution():
    ops = [ev("fusion.1", 1, 3), ev("fusion.2", 3, 4), ev("copy.1", 6, 7)]
    spans = [ev("bench.step_call", 0, 1.5), ev("bench.block_sync", 4, 10)]
    trace = Trace({0: ops}, spans, (0.0, 10.0))
    assert tracered.busy_seconds(trace) == {0: 4.0}
    gaps = dict(tracered.idle_gaps(trace))
    assert gaps == {"bench.block_sync": 5.0, "bench.step_call": 1.0}


def test_self_time_does_not_count_a_body_twice():
    ops = [ev("while.1", 0, 10), ev("fusion.1", 1, 4), ev("fusion.2", 5, 9),
           ev("copy.3", 10, 11)]
    trace = Trace({0: ops}, [], (0.0, 11.0))
    assert dict(tracered.top_ops(trace)) == {"fusion": 7.0, "while": 3.0, "copy": 1.0}


def test_collective_in_flight_and_exposed():
    ops = [
        ev("conditional.1", 0, 10),
        ev("collective-permute-start.1", 1, 1.5),
        ev("fusion.7", 2, 4),
        ev("collective-permute-done.1", 5, 8),
        ev("collective-permute.9", 20, 21),
    ]
    intervals = tracered.collective_intervals(ops)
    assert intervals == [(1.0, 8.0), (20.0, 21.0)]
    # fusion.7 hides 2 of the first 7 seconds; the enclosing conditional and
    # the collective's own events hide nothing.
    assert tracered.exposed_seconds(ops, intervals) == pytest.approx(6.0)


FIXTURE = os.path.join(BENCH, "fixtures")


@pytest.mark.skipif(
    not glob.glob(os.path.join(FIXTURE, "*.xplane.pb")),
    reason="no recorded trace in benchmark/fixtures",
)
def test_recorded_v5e_trace_reduces_to_known_numbers():
    (path,) = glob.glob(os.path.join(FIXTURE, "*.xplane.pb"))
    with open(os.path.join(FIXTURE, "expected.json")) as f:
        expected = json.load(f)
    trace = tracered.load(path)
    assert sorted(map(str, trace.device_ops)) == expected["devices"]
    assert len(trace.host_spans) == expected["host_spans"]
    window = trace.window[1] - trace.window[0]
    busy = tracered.busy_seconds(trace)
    idle = 100.0 * (1.0 - min(busy.values()) / window)
    assert idle == pytest.approx(expected["device_idle_share"], rel=1e-6)
    top = tracered.top_ops(trace, 10)
    assert [n for n, _ in top[:3]] == expected["top_ops"]
    assert top[0][1] == pytest.approx(expected["top_op_seconds"], rel=1e-6)
    gaps = tracered.idle_gaps(trace, 5)
    assert [n for n, _ in gaps] == expected["gap_spans"]


RECORD = dict(
    traced_steps=2, blocks=[0.02, 0.03, 0.025], dispatch_ms=[1.0, 2.0, 3.0],
    block_steps=2, leaf_sizes=[1000, 24], cell={"wire_dtype": "f32"},
    flops_per_sample=2.5e9, state_setup_s=8.0, compile_s=9.0,
    exchange_alone_ms=4.0, device_kind="TPU v5 lite",
    kernel_work={"flash_attention": {"flops": 1e9, "bytes": 1e6}},
)
# What each reader makes of the recorded trace and the record above; None
# where the trace holds nothing for it (a ResNet step has neither a flash
# kernel nor, on one chip, a collective).
EXPECTED_READINGS = {
    "state_setup_s": 8.0, "compile_s": 9.0, "step_ms_p50": 12.5,
    "host_dispatch_ms": 2.0, "exchange_bytes_per_step": 0.004096,
    "exchange_alone_ms": 4.0, "model_gflop_per_sample": 2.5,
    "collective_ms_per_step": None, "collective_exposed_ms": None,
    "attn_kernel_ms_per_step": None, "flash_attention_roofline": None,
    "device_idle_share": 20.08493487882882,
}


@pytest.mark.parametrize("name", sorted(EXPECTED_READINGS))
def test_reader_on_the_recorded_trace(name):
    import importlib

    (path,) = glob.glob(os.path.join(FIXTURE, "*.xplane.pb"))
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    got = reader.reduce(tracered.load(path), dict(RECORD))
    want = EXPECTED_READINGS[name]
    assert got is None if want is None else got == pytest.approx(want, rel=1e-9)


def test_every_reader_in_the_manifest_is_read_on_the_recorded_trace():
    from yardstick_paths import PER_LAYER

    assert set(PER_LAYER) <= set(EXPECTED_READINGS)


def test_kernel_and_collective_readers_on_hand_made_events():
    ops = [
        ev("flash_attention.4", 0, 2), ev("flash_mha_bwd_dq_block_k_128.9", 2, 5),
        ev("fusion.1", 5, 6), ev("collective-permute-start.1", 6, 6.5),
        ev("collective-permute-done.1", 7, 8), ev("fusion.2", 8, 10),
    ]
    trace = Trace({0: ops}, [ev("bench.step_call", 0, 10)], (0.0, 10.0))
    import importlib

    read = lambda name: importlib.import_module(
        "benchmark.layer_metrics." + name
    ).reduce(trace, dict(RECORD))
    assert read("attn_kernel_ms_per_step") == pytest.approx(2500.0)
    assert read("collective_ms_per_step") == pytest.approx(1000.0)
    assert read("collective_exposed_ms") == pytest.approx(1000.0)
    # 1e9 FLOPs at 197e12/s against 1e6 bytes at 819e9/s: compute-bound.
    assert read("flash_attention_roofline") == pytest.approx(
        100 * (1e9 / 197e12) * 2 / 5.0
    )
