"""Where the yardstick lives, for the tests of this directory."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
END_TO_END = [m["name"] for m in MANIFEST["end_to_end"]]


def load(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


def cell_files(name):
    """(entry, configuration file, traffic file) of a cell."""
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    config = next(c for c in MANIFEST["configs"] if c["name"] == entry["config"])
    return (
        entry, load(config["file"]),
        load(os.path.join("benchmark", "workloads", entry["traffic"] + ".json")),
    )


def run_benchmark(args, root=ROOT, pythonpath=None, script="benchmark/run.py"):
    """``python <script> <args>`` from ``root`` on the CPU, as a child with no
    forced device count of its own; returns the finished process."""
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    else:
        env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=900,
    )


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
