"""BENCHMARK.json is well-formed and every name in it resolves to a file."""

import importlib
import os
import re

import pytest
from yardstick_paths import (
    BENCH, CELLS, CONFIGS, END_TO_END, MANIFEST, PER_LAYER, ROOT, cell_files,
)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    names = CELLS + CONFIGS + PER_LAYER + END_TO_END
    assert len(set(CELLS)) == len(CELLS) and len(set(CONFIGS)) == len(CONFIGS)
    assert len(set(PER_LAYER + END_TO_END)) == len(PER_LAYER + END_TO_END)
    assert all(NAME.match(n) for n in names)


def test_four_chip_cells_within_the_cap():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_entry(name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    config = cell_files(next(
        w["name"] for w in MANIFEST["workloads"] if w["config"] == name
    ))[1]
    assert config["reduced"] == entry["reduced"]
    assert os.path.exists(
        os.path.join(BENCH, "builders", config["family"] + ".py")
    )
    widths = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_dim)$")
    assert not any(widths.search(k) for k in entry["reduced"])
    assert 1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"]) <= 200


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry(name):
    entry, config, cell = cell_files(name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["traffic"]) and entry["config"] in CONFIGS
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert cell["k"] % cell["block_steps"] == 0 and cell["warmup_steps"] >= 2
    assert cell["transport"] in ("stacked", "ici")
    # One peer a chip across chips, every replica on the one chip otherwise.
    assert entry["chips"] == (cell["peers"] if cell["transport"] == "ici" else 1)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("name", END_TO_END)
def test_end_to_end_metric(name):
    m = next(x for x in MANIFEST["end_to_end"] if x["name"] == name)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_matches_its_reader(name):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == name)
    assert set(m) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves",
    }
    assert m["moves"] in END_TO_END and m["source"] in SOURCES
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        m["layer"], m["unit"], m["moves"], m["source"],
    )
    # A reader that finds nothing to read returns nothing.
    empty = dict(
        traced_steps=0, blocks=[], dispatch_ms=[], block_steps=1,
        leaf_sizes=[], cell={"wire_dtype": "f32"}, flops_per_sample=0.0,
        state_setup_s=None, compile_s=None, kernel_work=None,
        device_kind="TPU v5 lite",
    )
    if m["source"] != "program_counter":
        assert reader.reduce(None, empty) is None
    if name.endswith("_roofline"):
        assert m["unit"] == "%"
