#!/usr/bin/env python
"""The BENCHMARK model at a spec topology: ResNet-20, 32 peers, random-pair.

VERDICT r3 missing #5: `spec_scale_train.py` proves 32/64-peer gossip
training converges — on SmallNet/digits — while ResNet-20 (the
BASELINE.json:8 benchmark model) had only been trained at 8 peers.  This
run closes that gap: ResNet-20 (GroupNorm — pure params) at the config-3
peer count (32, random-pair pool), on the 32-device emulated CPU mesh,
with the same offline CIFAR-10 stand-in as the round-3 convergence study
(digits upscaled to 32×32×3, standardized — real images, CIFAR's input
shape; see experiments/async_convergence.py).

Reduced budget for the 1-core box: 250 steps (VERDICT r3 prescribed
~150, but the 150-step probe left one replica mid-accuracy-ramp at 0.85
— 250 lets the ramp flatten), batch 16/peer, one seed, run at
background nice level.  The claim
this certifies is MIXING at the spec topology on the benchmark model —
every replica's accuracy in one band, consensus model at-or-above the
replica mean — not a headline accuracy (that is the 8-peer study's job).

→ artifacts/spec_scale_resnet20.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_PEERS = 32
STEPS = 250
BATCH = 16


def run() -> dict:
    import numpy as np

    from dpwa_tpu.utils.devices import ensure_devices

    ensure_devices(N_PEERS, mode="cpu")
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.data import peer_batches
    from dpwa_tpu.models.resnet import ResNet20
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
    from dpwa_tpu.train import (
        consensus_params,
        init_gossip_state,
        make_gossip_eval_fn,
        make_gossip_train_step,
        stack_params,
    )

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from async_convergence import _cifar_shaped_digits

    x_tr, y_tr, x_te, y_te = _cifar_shaped_digits(0)
    mu, sd = x_tr.mean(), x_tr.std()
    x_tr, x_te = (x_tr - mu) / sd, (x_te - mu) / sd

    cfg = make_local_config(
        N_PEERS, schedule="random", fetch_probability=0.5, pool_size=32,
    )
    transport = IciTransport(cfg, mesh=make_mesh(cfg))
    model = ResNet20()  # GroupNorm: pure params, gossip-able on all paths
    params0 = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    opt = optax.adam(1e-3)
    state = init_gossip_state(stack_params(params0, N_PEERS), opt, transport)

    def loss_fn(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        ).mean()

    step_fn = make_gossip_train_step(loss_fn, opt, transport)
    sh = peer_sharding(transport.mesh)
    batches = peer_batches(x_tr, y_tr, N_PEERS, BATCH, seed=0)
    t0 = time.time()
    for step in range(STEPS):
        bx, by = next(batches)
        state, losses, info = step_fn(
            state, (jax.device_put(bx, sh), jax.device_put(by, sh))
        )
        if step % 25 == 0:
            print(
                f"step {step} mean loss {float(np.asarray(losses).mean()):.3f} "
                f"({time.time()-t0:.0f}s)",
                file=sys.stderr, flush=True,
            )
    eval_fn = make_gossip_eval_fn(model.apply, transport)
    accs = np.asarray(
        eval_fn(state.params, jnp.asarray(x_te), jnp.asarray(y_te))
    )
    cons = consensus_params(state.params)
    cons_logits = model.apply(cons, jnp.asarray(x_te))
    cons_acc = float(np.mean(np.argmax(np.asarray(cons_logits), -1) == y_te))
    return {
        "experiment": "spec_scale_resnet20",
        "layout": "config3: 32 peers, random-pair (pool 32), fetch_p 0.5",
        "model": "ResNet-20 (GroupNorm), Adam(1e-3)",
        "task": (
            "digits upscaled to 32x32x3, standardized (offline CIFAR-10 "
            "stand-in; see async_convergence.py)"
        ),
        "steps": STEPS,
        "batch_per_peer": BATCH,
        "seconds": round(time.time() - t0, 1),
        "final_acc_mean": round(float(accs.mean()), 4),
        "final_acc_min": round(float(accs.min()), 4),
        "final_acc_max": round(float(accs.max()), 4),
        "replica_acc_spread": round(float(accs.max() - accs.min()), 4),
        "consensus_model_acc": round(cons_acc, 4),
        "note": (
            "reduced-budget mixing witness at the spec topology on the "
            "benchmark model: one band of replica accuracies + consensus "
            ">= mean certifies global mixing; headline accuracy lives in "
            "the 8-peer study (artifacts/async_convergence_resnet20/)"
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="(internal) run in this process")
    args = ap.parse_args()
    if args.inner:
        print("RESULT " + json.dumps(run()), flush=True)
        return
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_PEERS}"
    ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--inner"],
        capture_output=True, text=True, timeout=7200, env=env, cwd=REPO,
    )
    sys.stderr.write(proc.stderr[-3000:] if proc.stderr else "")
    if proc.returncode != 0:
        raise RuntimeError(f"inner run failed rc={proc.returncode}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            out = json.loads(line[len("RESULT "):])
            path = os.path.join(REPO, "artifacts", "spec_scale_resnet20.json")
            # Atomic write: this artifact is ~30 min of 1-core compute.
            with open(path + ".tmp", "w") as f:
                json.dump(out, f, indent=1)
            os.replace(path + ".tmp", path)
            print(json.dumps(out, indent=1))
            return
    raise RuntimeError("no RESULT line from inner run")


if __name__ == "__main__":
    main()
