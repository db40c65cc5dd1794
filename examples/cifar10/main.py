#!/usr/bin/env python
"""CIFAR-10 ResNet-20, 8-peer ring gossip — the headline config.

BASELINE.json:8 and the north-star metric (steps/sec to target accuracy +
pairwise-avg bandwidth).  One SPMD process drives all 8 peers; each peer
trains ResNet-20 on its own shard and ring-gossips parameters every step.

CIFAR-10 is loaded from disk if present (``--data-dir`` pointing at a
``cifar-10-batches-py`` directory or an npz); with no dataset on this
zero-egress box, ``--synthetic`` trains on generated 32×32 data — still the
real model, schedule, and exchange, so throughput numbers are valid; only
accuracy is meaningless then (and is labeled as such)."""

from __future__ import annotations

import argparse
import os
import pickle
import sys
import time

import numpy as np

# Runnable straight from a checkout, no install needed.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def load_cifar10(data_dir: str):
    """CIFAR-10 from the canonical python pickle batches or an npz."""
    npz = os.path.join(data_dir, "cifar10.npz")
    if os.path.exists(npz):
        with np.load(npz) as d:
            return (
                d["x_train"].astype(np.float32) / 255.0,
                d["y_train"].astype(np.int32),
                d["x_test"].astype(np.float32) / 255.0,
                d["y_test"].astype(np.int32),
            )
    batch_dir = os.path.join(data_dir, "cifar-10-batches-py")
    if os.path.isdir(batch_dir):
        xs, ys = [], []
        for i in range(1, 6):
            with open(os.path.join(batch_dir, f"data_batch_{i}"), "rb") as f:
                d = pickle.load(f, encoding="bytes")
            xs.append(d[b"data"])
            ys.append(d[b"labels"])
        x_tr = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y_tr = np.concatenate(ys)
        with open(os.path.join(batch_dir, "test_batch"), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        x_te = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y_te = np.asarray(d[b"labels"])
        return (
            x_tr.astype(np.float32) / 255.0,
            y_tr.astype(np.int32),
            x_te.astype(np.float32) / 255.0,
            y_te.astype(np.int32),
        )
    raise FileNotFoundError(f"no CIFAR-10 under {data_dir}")


def synthetic_cifar(n_train=4096, n_test=512, seed=0):
    rng = np.random.default_rng(seed)
    x_tr = rng.random((n_train, 32, 32, 3), np.float32)
    y_tr = rng.integers(0, 10, n_train).astype(np.int32)
    x_te = rng.random((n_test, 32, 32, 3), np.float32)
    y_te = rng.integers(0, 10, n_test).astype(np.int32)
    return x_tr, y_tr, x_te, y_te


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--config",
        default=os.path.join(os.path.dirname(__file__), "nodes.yaml"),
    )
    ap.add_argument("--data-dir", default="/root/datasets")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    from dpwa_tpu.utils.launch import add_transport_args, build_transport

    add_transport_args(ap)
    args = ap.parse_args()

    from dpwa_tpu.config import load_config

    here = os.path.dirname(os.path.abspath(__file__))
    cfg_path = (
        args.config
        if os.path.exists(args.config)
        else os.path.join(here, args.config)
    )
    cfg = load_config(cfg_path)
    bundle = build_transport(
        cfg, args.transport, args.devices, wire_dtype=args.wire_dtype
    )
    cfg = bundle.config  # effective config (wire_dtype applied)

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.data import device_prefetch, peer_batches
    from dpwa_tpu.metrics import MetricsLogger
    from dpwa_tpu.models.resnet import ResNet20
    from dpwa_tpu.train import (
        init_params_per_peer,
        make_gossip_eval_fn,
    )
    from dpwa_tpu.utils.pytree import tree_wire_bytes

    try:
        x_tr, y_tr, x_te, y_te = load_cifar10(args.data_dir)
        dataset = "cifar10"
    except FileNotFoundError:
        if not args.synthetic:
            print(
                "no CIFAR-10 on disk; rerun with --synthetic for throughput "
                "measurement on generated data",
                file=sys.stderr,
            )
            args.synthetic = True
        x_tr, y_tr, x_te, y_te = synthetic_cifar()
        dataset = "synthetic-cifar-shaped"

    n = cfg.n_peers
    transport = bundle.transport
    init_state, make_step = bundle.init_state, bundle.make_step
    eval_transport = bundle.eval_transport
    batch_sharding = bundle.batch_sharding
    model = ResNet20(dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    init = lambda k: model.init(k, jnp.zeros((1, 32, 32, 3)))
    stacked = init_params_per_peer(init, jax.random.key(0), n)
    opt = optax.chain(
        optax.sgd(args.lr, momentum=0.9),
    )
    state = init_state(stacked, opt, transport)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    step_fn = make_step(loss_fn, opt, transport)
    payload = tree_wire_bytes(
        jax.tree.map(lambda v: v[0], state.params),
        cfg.protocol.wire_dtype,
    )
    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    if args.synthetic:
        # Synthetic throughput mode: pre-stage a small pool of device
        # batches and cycle.  Regenerating + re-shipping host batches
        # every step measures numpy and the host→device link, not the
        # training system.
        import itertools

        gen = peer_batches(
            x_tr, y_tr, n, args.batch_size, seed=cfg.protocol.seed
        )
        pool = [
            tuple(jax.device_put(b, batch_sharding) for b in next(gen))
            for _ in range(4)
        ]
        batches = itertools.cycle(pool)
    else:
        batches = device_prefetch(
            peer_batches(
                x_tr, y_tr, n, args.batch_size, seed=cfg.protocol.seed
            ),
            sharding=batch_sharding,
        )

    # Warmup/compile outside the timed region.
    state, losses, info = step_fn(state, next(batches))
    jax.block_until_ready((state, losses))
    # Metric values are RETAINED (tiny per-step device scalars, with
    # their step-time stamps) and written after timing: materializing a
    # device value mid-loop blocks on the whole in-flight pipeline,
    # which would measure host↔device sync latency instead of training
    # throughput.  The finally block flushes whatever was collected even
    # if the run dies mid-loop.
    records = []
    try:
        t0 = time.perf_counter()
        for step in range(1, args.steps):
            state, losses, info = step_fn(state, next(batches))
            if step % metrics.every == 0:
                records.append((step, metrics.elapsed(), losses, info))
        jax.block_until_ready((state, losses))  # the whole pipeline
        dt = time.perf_counter() - t0
    finally:
        for step, t_rec, losses_rec, info_rec in records:
            metrics.log_exchange(
                step, losses_rec, info_rec, payload_bytes=payload, t=t_rec
            )
        metrics.close()
    steps_per_sec = (args.steps - 1) / dt

    eval_fn = make_gossip_eval_fn(model.apply, eval_transport)
    accs = np.asarray(eval_fn(state.params, jnp.asarray(x_te), jnp.asarray(y_te)))
    acc_note = "" if dataset == "cifar10" else " (synthetic labels: chance-level)"
    plat = jax.devices()[0].platform
    ndev = 1 if args.transport == "stacked" else n
    print(f"dataset: {dataset}")
    print(
        f"steps/sec (all {n} peers, incl. exchange, on {plat} x{ndev}): "
        f"{steps_per_sec:.3f}"
    )
    print(f"mean test accuracy: {accs.mean():.4f}{acc_note}")


if __name__ == "__main__":
    main()
