#!/usr/bin/env python
"""Quantify random-schedule pool truncation vs fresh uniform matchings.

`lax.ppermute` needs static permutations, so the `random` schedule
compiles a POOL of matchings (config `pool_size`; this study motivated
changing the default from the historical 16 to auto = clamp(2n, 16,
128)) and draws an i.i.d. pool index per step (`pool_branch_draw`).  The reference draws
a FRESH matching every step [R] — statistically wider: at n=8 there are
105 perfect matchings, at n=64 astronomically many, and a pool carries
its K forever.  This study measures what that truncation actually costs,
at n ∈ {8, 32, 64} and pool_size ∈ {4, 16, 64, 128, 256}:

- **pair coverage** — fraction of the n(n-1)/2 unordered pairs that can
  ever meet (a pair absent from every pool matching never exchanges
  directly);
- **meeting-frequency TV distance** — total-variation gap between the
  empirical per-pair meeting distribution over S steps and the uniform
  1/P the fresh-draw process targets (the fresh arm's own TV at the same
  S is the finite-sample floor);
- **mixing steps** — gossip rounds (α = 0.5, full participation) until
  the replica std contracts below 1e-6 of its start, the functional
  metric gossip SGD cares about.

The pool arm runs the REAL schedule (`build_schedule` + its threefry
pool-index draws), not a reimplementation; the fresh arm applies a new
uniform matching per step.

→ artifacts/pool_truncation.json
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Host-side simulation; the schedule's threefry draws go through jax —
# pin CPU before first use (a count, not a rate: no chip is needed).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.parallel.schedules import (  # noqa: E402
    _random_matching,
    build_schedule,
)

NS = (8, 32, 64)
POOL_SIZES = (4, 16, 64, 128, 256)
SEEDS = (0, 1)
S_STATS = 1500  # steps for meeting-frequency statistics
MIX_TOL = 1e-6
MIX_CAP = 5000


def _pair_indices(n: int) -> dict:
    pairs = {}
    k = 0
    for i in range(n):
        for j in range(i + 1, n):
            pairs[(i, j)] = k
            k += 1
    return pairs


def run_arm(n: int, pairing_fn, pool_perms=None) -> dict:
    """One simulation: meeting counts over S_STATS steps + mixing curve.

    ``pairing_fn(step) -> perm``; ``pool_perms`` (pool arm only) gives
    static coverage without sampling."""
    pairs = _pair_indices(n)
    counts = np.zeros(len(pairs), np.int64)
    x = np.arange(n, dtype=np.float64)
    std0 = x.std()
    idx = np.arange(n)
    mix_steps = None
    for step in range(max(S_STATS, MIX_CAP)):
        perm = np.asarray(pairing_fn(step))
        if step < S_STATS:
            for i in range(n):
                j = int(perm[i])
                if j > i:
                    counts[pairs[(i, j)]] += 1
        if mix_steps is None:
            x = np.where(perm == idx, x, 0.5 * (x + x[perm]))
            if x.std() / std0 < MIX_TOL:
                mix_steps = step + 1
        if mix_steps is not None and step >= S_STATS - 1:
            break
    p_emp = counts / max(counts.sum(), 1)
    p_uni = np.full(len(pairs), 1.0 / len(pairs))
    tv = 0.5 * float(np.abs(p_emp - p_uni).sum())
    if pool_perms is not None:
        covered = set()
        for perm in pool_perms:
            for i in range(n):
                j = int(perm[i])
                if j > i:
                    covered.add((i, j))
        coverage = len(covered) / len(pairs)
    else:
        coverage = float(np.mean(counts > 0))
    return {
        "pair_coverage": round(float(coverage), 4),
        "meeting_tv_distance": round(tv, 4),
        "mixing_steps_to_1e-6": mix_steps if mix_steps is not None else MIX_CAP,
    }


def study(n: int) -> dict:
    out = {"n": n, "pools": {}, "fresh": None}
    fresh_runs = []
    for seed in SEEDS:
        rng = np.random.default_rng(1000 + seed)
        fresh_runs.append(run_arm(n, lambda step: _random_matching(n, rng)))
    out["fresh"] = _avg(fresh_runs)
    for k in POOL_SIZES:
        runs = []
        for seed in SEEDS:
            sched = build_schedule(
                make_local_config(
                    n, schedule="random", pool_size=k,
                    fetch_probability=1.0, seed=seed,
                )
            )
            perms = [sched.pool[i] for i in range(sched.pool_size)]
            runs.append(run_arm(n, sched.pairing, pool_perms=perms))
        out["pools"][str(k)] = _avg(runs)
    return out


def _avg(runs) -> dict:
    return {
        key: round(float(np.mean([r[key] for r in runs])), 4)
        for key in runs[0]
    }


def main() -> None:
    results = [study(n) for n in NS]
    out = {
        "experiment": "pool_truncation",
        "steps_for_stats": S_STATS,
        "seeds": len(SEEDS),
        "note": (
            "random-schedule pool (real build_schedule path, i.i.d. "
            "threefry pool draws) vs fresh uniform matchings; TV is vs "
            "the uniform per-pair meeting distribution, the fresh arm's "
            "TV at the same S is the finite-sample floor"
        ),
        "results": results,
    }
    path = os.path.join(REPO, "artifacts", "pool_truncation.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
