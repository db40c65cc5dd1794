#!/usr/bin/env python
"""Schedule-level mixing at spec scale (128 peers): committed curves.

BASELINE.json's configs name 32/64/128-peer topologies; the round-2
hierarchical bug was exactly the class of defect that only shows past
the tested scale.  `tests/test_schedules.py` asserts contraction at
n=128 for every schedule family; this experiment records the actual
mixing CURVES (std of replica values vs gossip round, α=0.5, full
participation) so the rates are inspectable, not just pass/fail.

→ artifacts/mixing_128.json
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Pure host-side simulation, but the schedules' threefry draws go through
# jax — pin it to CPU before first use (a count, not a rate: no chip is
# needed).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.parallel.schedules import build_schedule  # noqa: E402

N = 128
CONFIGS = [
    ("ring", "ring", {}),
    ("random", "random", {"pool_size": 64}),
    ("hierarchical_8groups_of_16", "hierarchical",
     {"group_size": 16, "inter_period": 4}),
    ("hierarchical_16groups_of_8", "hierarchical",
     {"group_size": 8, "inter_period": 2}),
    ("exponential", "exponential", {}),
]
CHECKPOINT_STEPS = (7, 21, 63, 189, 567, 1701, 5103, 15309)


def simulate(label: str, schedule: str, kwargs: dict) -> dict:
    sched = build_schedule(
        make_local_config(N, schedule=schedule, fetch_probability=1.0, **kwargs)
    )
    x = np.arange(N, dtype=np.float64)
    idx = np.arange(N)
    std0 = float(x.std())
    curve = {}
    steps = max(CHECKPOINT_STEPS)
    for step in range(steps + 1):
        if step in CHECKPOINT_STEPS or step == sched.period:
            curve[step] = float(x.std() / std0)
        perm = sched.pairing(step)
        x = np.where(perm == idx, x, 0.5 * (x + x[perm]))
        if x.std() / std0 < 1e-14:
            curve[step + 1] = float(x.std() / std0)
            break
    return {
        "label": label,
        "schedule": schedule,
        **kwargs,
        "period": int(sched.period),
        "distinct_pairings": int(sched.pool_size),
        "std_over_std0_by_step": curve,
    }


def main() -> None:
    out = {
        "experiment": "mixing_128",
        "n_peers": N,
        "note": (
            "normalized replica-value std vs gossip round, alpha=0.5, "
            "full participation; exponential hits exact consensus in one "
            "log2(n)=7-slot pass, hierarchical in O(period) rounds, ring "
            "in O(n^2) rounds"
        ),
        "results": [simulate(lbl, s, k) for lbl, s, k in CONFIGS],
    }
    path = os.path.join(REPO, "artifacts", "mixing_128.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({r["label"]: r["std_over_std0_by_step"] for r in out["results"]}, indent=1))


if __name__ == "__main__":
    main()
