#!/usr/bin/env python
"""Real gossip TRAINING at spec-scale peer counts (configs 3/4 layouts).

The dryrun artifacts prove the 32/64-device layouts compile and execute
one step; the mixing artifact proves the schedules contract at n=128.
This experiment closes the remaining gap: actual multi-step training
convergence at the spec peer counts, on the emulated CPU mesh —

- config-3 layout: 32 peers, random-pair schedule;
- config-4 layout: 64 peers, hierarchical (8 groups of 8) — the regime
  where the round-2 disconnection bug would have silently broken global
  consensus.

SmallNet on the offline digits (per-peer disjoint shards, batch 16), so
a 64-replica run fits this box's single CPU core in minutes.  Records
per-layout final accuracy and replica spread (consensus quality) →
artifacts/spec_scale_train.json.

Each layout runs in its own subprocess: XLA fixes the forced device
count per process.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LAYOUTS = {
    "config3-32peer-random": dict(n=32, schedule="random", kwargs={"pool_size": 32}),
    "config4-64peer-hierarchical-8x8": dict(
        n=64, schedule="hierarchical", kwargs={"group_size": 8, "inter_period": 3}
    ),
    # inter_period sweep at the config-4 topology (VERDICT r3 weak #5: is
    # the 64-peer replica spread cadence-limited or protocol-inherent?).
    # ip=3 is the default layout above; 2 and 4 bracket it.
    "config4-64peer-hierarchical-8x8-ip2": dict(
        n=64, schedule="hierarchical", kwargs={"group_size": 8, "inter_period": 2}
    ),
    "config4-64peer-hierarchical-8x8-ip4": dict(
        n=64, schedule="hierarchical", kwargs={"group_size": 8, "inter_period": 4}
    ),
}
DEFAULT_LAYOUTS = (
    "config3-32peer-random",
    "config4-64peer-hierarchical-8x8",
)
SWEEP_LAYOUTS = (
    "config4-64peer-hierarchical-8x8-ip2",
    "config4-64peer-hierarchical-8x8",
    "config4-64peer-hierarchical-8x8-ip4",
)
STEPS = 400
BATCH = 16


def train_digits_gossip(
    n: int,
    schedule: str,
    schedule_kwargs: dict,
    *,
    steps: int = STEPS,
    batch: int = BATCH,
    fetch_probability: float = 0.5,
    seed: int = 0,
):
    """The shared spec-scale training substrate: real n-peer ICI gossip
    on the emulated CPU mesh, SmallNet on offline digits with per-peer
    disjoint shards.

    One definition used by BOTH `spec_scale_train.py` (layout/topology
    witnesses) and `pool_convergence.py` (pool-size sweep), so the two
    experiments can never silently measure different substrates.
    ``seed`` keys the schedule/participation RNG, the param init, and
    the batch stream together.  Returns (per-replica accuracies,
    consensus-model accuracy)."""
    import numpy as np

    from dpwa_tpu.utils.devices import ensure_devices

    ensure_devices(n, mode="cpu")
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.data import load_digits_dataset, peer_batches
    from dpwa_tpu.models.mnist import SmallNet
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
    from dpwa_tpu.train import (
        consensus_params,
        init_gossip_state,
        make_gossip_eval_fn,
        make_gossip_train_step,
        stack_params,
    )

    cfg = make_local_config(
        n, schedule=schedule, fetch_probability=fetch_probability,
        seed=seed, **schedule_kwargs,
    )
    transport = IciTransport(cfg, mesh=make_mesh(cfg))
    x_tr, y_tr, x_te, y_te = load_digits_dataset()
    model = SmallNet()
    params0 = model.init(jax.random.key(seed), jnp.zeros((1, 8, 8, 1)))
    opt = optax.sgd(0.05, momentum=0.9)
    state = init_gossip_state(stack_params(params0, n), opt, transport)

    def loss_fn(params, batch_):
        x, y = batch_
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        ).mean()

    step_fn = make_gossip_train_step(loss_fn, opt, transport)
    sh = peer_sharding(transport.mesh)
    batches = peer_batches(x_tr, y_tr, n, batch, seed=seed)
    for _ in range(steps):
        bx, by = next(batches)
        state, _, _ = step_fn(
            state, (jax.device_put(bx, sh), jax.device_put(by, sh))
        )
    eval_fn = make_gossip_eval_fn(model.apply, transport)
    accs = np.asarray(
        eval_fn(state.params, jnp.asarray(x_te), jnp.asarray(y_te))
    )
    cons = consensus_params(state.params)
    cons_logits = model.apply(cons, jnp.asarray(x_te))
    cons_acc = float(np.mean(np.argmax(np.asarray(cons_logits), -1) == y_te))
    return accs, cons_acc


def run_layout(name: str) -> dict:
    spec = LAYOUTS[name]
    accs, cons_acc = train_digits_gossip(
        spec["n"], spec["schedule"], spec["kwargs"]
    )
    return {
        "layout": name,
        "n_peers": spec["n"],
        "schedule": spec["schedule"],
        **spec["kwargs"],
        "steps": STEPS,
        "batch_per_peer": BATCH,
        "final_acc_mean": round(float(accs.mean()), 4),
        "final_acc_min": round(float(accs.min()), 4),
        "final_acc_max": round(float(accs.max()), 4),
        "replica_acc_spread": round(float(accs.max() - accs.min()), 4),
        "consensus_model_acc": round(cons_acc, 4),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", choices=sorted(LAYOUTS), default=None)
    ap.add_argument(
        "--sweep-inter-period", action="store_true",
        help="run the 64-peer hierarchical layout at inter_period 2/3/4 "
        "and write artifacts/hier_inter_period_sweep.json instead",
    )
    args = ap.parse_args()
    if args.layout:
        print("RESULT " + json.dumps(run_layout(args.layout)), flush=True)
        return

    layout_names = SWEEP_LAYOUTS if args.sweep_inter_period else DEFAULT_LAYOUTS
    results = []
    for name in layout_names:
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        # Append (not clobber): keep any operator-exported XLA flags.
        # Flags in the launch env are the reliable path (XLA parses them
        # once per process).
        count = f"--xla_force_host_platform_device_count={LAYOUTS[name]['n']}"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + count).strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--layout", name],
            capture_output=True, text=True, timeout=3600, env=env, cwd=REPO,
        )
        if proc.returncode != 0:
            print(proc.stderr[-2000:], file=sys.stderr)
            raise RuntimeError(f"{name} failed rc={proc.returncode}")
        found = False
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                row = json.loads(line[len("RESULT "):])
                results.append(row)
                found = True
                print(row, file=sys.stderr, flush=True)
        if not found:
            raise RuntimeError(
                f"{name} exited 0 without a RESULT line; refusing to "
                f"write a partial artifact:\n{proc.stdout[-1000:]}"
            )
    if args.sweep_inter_period:
        out = {
            "experiment": "hier_inter_period_sweep",
            "task": "sklearn digits 8x8, SmallNet, SGD(0.05, m=0.9)",
            "note": (
                "64 peers / 8 groups at inter_period 2/3/4, same steps/"
                "seed: if replica_acc_spread shrinks with more frequent "
                "cross-group slots (smaller inter_period), the round-3 "
                "0.064 spread is cadence-limited (tunable); if flat, it "
                "is inherent to two-level gossip at this scale"
            ),
            "results": results,
        }
        path = os.path.join(REPO, "artifacts", "hier_inter_period_sweep.json")
    else:
        out = {
            "experiment": "spec_scale_train",
            "task": "sklearn digits 8x8, SmallNet, SGD(0.05, m=0.9)",
            "note": (
                "multi-step gossip training convergence at the spec peer "
                "counts on the emulated CPU mesh; replica_acc_spread ~0 and "
                "consensus_model_acc ~ final_acc_mean certify global mixing "
                "(the round-2 hierarchical bug would have left group-level "
                "accuracy islands at 8 groups)"
            ),
            "results": results,
        }
        path = os.path.join(REPO, "artifacts", "spec_scale_train.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["results"], indent=1))


if __name__ == "__main__":
    main()
