#!/usr/bin/env python3
"""The plain single-worker baseline that fixes a cell's ``loss_ceiling``.

    python benchmark/single_worker.py --workload <name> --seed <n>

One peer, the cell's per-peer batch, the same task, optimizer and K, no
exchange and nothing of the program but the model: a plain ``optax`` loop.
Prints the loss the cell's ``loss_at_k`` is read against (the mean over the
cell's last ``loss_steps`` steps up to K).  Paid once, when a cell is defined,
and not in every run; the builder writes the result plus a stated margin into
the cell file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark import run, traffic

    loaded = run.load_cell(args.workload, args.rehearse_cpu)
    config, cell, builder = loaded.config, loaded.traffic, loaded.builder
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("single_worker.py needs a TPU", file=sys.stderr)
        return run.EXIT_NO_ACCELERATOR
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    built = builder.build(config, dict(cell, peers=1))
    key = jax.random.key(args.seed)
    params = jax.jit(built.init_fn)(jax.random.fold_in(key, 0))
    optimizer = built.make_optimizer(
        jax.eval_shape(built.init_fn, jax.random.key(0))
    )
    opt_state = jax.jit(optimizer.init)(params)
    generate = traffic.make_generator(
        cell["task"], built.batch_shape, 1, cell["per_peer_batch"]
    )
    data_key = jax.random.fold_in(key, 1)
    pool = [generate(data_key, i) for i in range(cell["pool_batches"])]

    @jax.jit
    def step(params, opt_state, batch):
        one = jax.tree.map(lambda v: v[0], batch)
        loss, grads = jax.value_and_grad(built.loss_fn)(params, one)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for i in range(cell["k"]):
        params, opt_state, loss = step(params, opt_state, pool[i % len(pool)])
        losses.append(loss)
    losses = np.asarray(jnp.stack(losses), np.float64)
    device = jax.devices()[0]
    print(json.dumps(dict(
        workload=args.workload, seed=args.seed, k=cell["k"],
        loss_first=float(losses[0]),
        loss_at_k=float(np.mean(
            losses[-cell.get("loss_steps", cell["block_steps"]):]
        )),
        device=dict(platform=device.platform, kind=device.device_kind),
    )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
