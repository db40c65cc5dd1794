"""``run.py --rehearse-cpu`` speaks the contract's last line in every cell,
and ``run.py`` without a chip says nothing."""

import pytest
from yardstick_paths import CELLS, MANIFEST, last_line, run_benchmark

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_contract_line_with_null_rates(name):
    proc = run_benchmark([
        "--workload", name, "--seed", "3", "--seconds", "1", "--trace", "0",
        "--rehearse-cpu",
    ])
    result = last_line(proc)
    assert set(result) == RESULT_KEYS and set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["device"]["platform"] == "cpu"
    reported = {
        m["name"]: m for m in MANIFEST["end_to_end"]
        if name in m.get("workloads", [name])
    }
    assert set(result["metrics"]) == set(reported)
    for metric, got in result["metrics"].items():
        assert got["unit"] == reported[metric]["unit"]
        if metric == "loss_at_k":
            assert got["value"] > 0
        else:  # a CPU run gives no time, rate or share of the device
            assert got["value"] is None
    # The lines before the last say what was checked.
    phases = [l for l in proc.stdout.splitlines() if '"phase"' in l]
    assert len(phases) == 2


def test_without_a_chip_there_is_no_result():
    proc = run_benchmark([
        "--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0",
    ])
    assert proc.returncode != 0 and proc.stdout == ""
    assert "TPU" in proc.stderr


def test_unknown_workload_is_refused():
    proc = run_benchmark(["--workload", "no-such-cell", "--rehearse-cpu"])
    assert proc.returncode != 0 and proc.stdout == ""
