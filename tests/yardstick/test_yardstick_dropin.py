"""A later PR adds a cell, a configuration and a per-layer metric by adding
files, with no edit to ``run.py`` or to any file that was there; a traced
rehearsal reads them; a compilation inside the window is reported."""

import filecmp
import json
import os
import shutil
import sys
import textwrap

import pytest
from yardstick_paths import BENCH, ROOT, last_line, load, run_benchmark

NEW_METRIC = textwrap.dedent('''
    """Blocks the window completed (a count)."""

    LAYER = "step builders"
    UNIT = "blocks"
    MOVES = "samples_per_s"
    SOURCE = "program_counter"


    def reduce(trace, record):
        return len(record["blocks"])
''')


@pytest.fixture(scope="module")
def copy_with_three_new_files(tmp_path_factory):
    root = tmp_path_factory.mktemp("later_pr")
    shutil.copytree(
        BENCH, root / "benchmark",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    manifest = load("BENCHMARK.json")
    config = load("benchmark/configs/mistral-7b-v0.3-lora.json")
    config.update(hidden_size=2048, num_attention_heads=16,
                  num_key_value_heads=4, intermediate_size=8192)
    cell = load("benchmark/workloads/lora-stacked2-t512.json")
    cell.update(peers=4, seq_len=1024, schedule="random", pool_size=4)
    files = {
        "benchmark/configs/small-decoder-lora.json": json.dumps(config),
        "benchmark/workloads/lora-stacked4-t1024.json": json.dumps(cell),
        "benchmark/layer_metrics/blocks_counted.py": NEW_METRIC,
    }
    for relative, text in files.items():
        (root / relative).write_text(text)
    manifest["configs"].append(dict(
        name="small-decoder-lora", source="a test", reduced=["num_hidden_layers"],
        file="benchmark/configs/small-decoder-lora.json", why="a test",
    ))
    manifest["workloads"].append(dict(
        name="small-lora-stacked4-t1024", config="small-decoder-lora",
        traffic="lora-stacked4-t1024", chips=1, why="a test",
    ))
    manifest["per_layer"].append(dict(
        name="blocks_counted", unit="blocks", better="higher",
        source="program_counter", layer="step builders",
        moves="samples_per_s", workloads=["small-lora-stacked4-t1024"],
    ))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root, set(files)


def test_fifth_cell_third_configuration_and_new_metric_are_only_data(
    copy_with_three_new_files,
):
    root, added = copy_with_three_new_files
    proc = run_benchmark(
        ["--workload", "small-lora-stacked4-t1024", "--seed", "5",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        root=str(root), pythonpath=ROOT,
    )
    result = last_line(proc)
    assert result["correct"] is True
    assert result["metrics"]["blocks_counted"]["value"] >= 1
    assert result["metrics"]["exchange_bytes_per_step"]["value"] > 0
    assert result["metrics"]["step_ms_p50"]["value"] is None
    assert len(result["breakdown"]["device_ops"]) <= 10
    # Nothing that was there was edited.
    compared = filecmp.dircmp(BENCH, root / "benchmark", ignore=["out", "__pycache__"])
    assert not compared.diff_files
    assert not filecmp.dircmp(
        os.path.join(BENCH, "layer_metrics"), root / "benchmark" / "layer_metrics",
        ignore=["__pycache__"],
    ).diff_files


def test_benchmark_alone_is_no_benchmark(copy_with_three_new_files):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, and no result."""
    root, _ = copy_with_three_new_files
    proc = run_benchmark(
        ["--workload", "small-lora-stacked4-t1024", "--rehearse-cpu"],
        root=str(root),
    )
    assert proc.returncode != 0
    assert not any(l.startswith('{"correct"') for l in proc.stdout.splitlines())


FORCED = textwrap.dedent('''
    import sys
    sys.path.insert(0, {root!r})
    import jax
    import jax.numpy as jnp
    from benchmark import run, traffic

    honest = traffic.make_generator

    def with_one_odd_batch(*args, **kwargs):
        generate = honest(*args, **kwargs)
        # The third batch of the pool is the first the window uses; its
        # labels have another dtype, so the step compiles again there.
        return lambda key, i: generate(key, i) if i != 2 else jax.tree.map(
            lambda v: v.astype(jnp.uint32), generate(key, i))

    traffic.make_generator = with_one_odd_batch
    sys.exit(run.main(["--workload", "mistral7b-lora-stacked2-t512", "--seed", "1",
                       "--seconds", "1", "--trace", "0", "--rehearse-cpu"]))
''')


def test_a_compilation_inside_the_window_fails_the_run(tmp_path):
    script = tmp_path / "forced.py"
    script.write_text(FORCED.format(root=ROOT))
    result = last_line(run_benchmark([], script=str(script)))
    assert result["correct"] is False
    assert result["failed"] >= 2  # every step of the block that compiled
