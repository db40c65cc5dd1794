#!/usr/bin/env python
"""MNIST gossip training — the reference's example, both transports.

Reference contract (SURVEY.md §3.1/§3.4, BASELINE.json:7): N processes, one
per YAML node, each launched with its node ``--name`` and the shared config;
no launcher daemon — the YAML file is the cluster.

TCP (reference-equivalent, one process per node)::

    python main.py --name node0 --config nodes.yaml --transport tcp &
    python main.py --name node1 --config nodes.yaml --transport tcp &

ICI (TPU-native: one SPMD process drives every peer)::

    python main.py --config nodes.yaml --transport ici

Stacked (single chip, N virtual peers — no mesh needed)::

    python main.py --config nodes.yaml --transport stacked

Uses full MNIST if found on disk, else the bundled 8×8 digits (this box has
no network egress; see dpwa_tpu.data)."""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

# Runnable straight from a checkout, no install needed.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def build_model(image_shape):
    import flax.linen as nn

    from dpwa_tpu.models.mnist import ConvNet, SmallNet

    return ConvNet() if image_shape[0] >= 28 else SmallNet()


def make_loss(model):
    import optax

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    return loss_fn


def run_tcp(args) -> None:
    """Per-process worker: the reference's deployment model."""
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.adapters.tcp_adapter import DpwaTcpAdapter
    from dpwa_tpu.config import load_config
    from dpwa_tpu.data import load_mnist_or_digits, peer_split
    from dpwa_tpu.metrics import MetricsLogger

    cfg = load_config(args.config)
    me = cfg.node_index(args.name)
    x_tr, y_tr, x_te, y_te, dataset = load_mnist_or_digits()
    xs, ys = peer_split(x_tr, y_tr, cfg.n_peers, seed=cfg.protocol.seed)
    x_my, y_my = xs[me], ys[me]

    model = build_model(x_tr.shape[1:])
    params = model.init(jax.random.key(me), jnp.zeros((1,) + x_tr.shape[1:]))
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)
    loss_fn = make_loss(model)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    adapter = DpwaTcpAdapter(params, args.name, cfg)
    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    rng = np.random.default_rng(1000 + me)
    try:
        for step in range(args.steps):
            idx = rng.integers(0, len(x_my), size=args.batch_size)
            batch = (jnp.asarray(x_my[idx]), jnp.asarray(y_my[idx]))
            loss, grads = grad_fn(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = adapter.update(float(loss), params)
            metrics.log(
                step,
                node=args.name,
                loss=float(loss),
                alpha=adapter.last_alpha,
                partner=adapter.last_partner,
            )
        logits = model.apply(params, jnp.asarray(x_te))
        acc = float(np.mean(np.argmax(np.asarray(logits), -1) == y_te))
        print(f"[{args.name}] {dataset} test accuracy: {acc:.4f}")
    finally:
        adapter.close()


def run_single_process(args, stacked: bool) -> None:
    """One process drives every peer: SPMD over a device mesh
    (``--transport ici``) or a stacked virtual-peer axis on one device
    (``--transport stacked``).  Same data, model, loop, and report."""
    from dpwa_tpu.config import load_config
    from dpwa_tpu.utils.launch import build_transport

    bundle = build_transport(
        load_config(args.config),
        "stacked" if stacked else "ici",
        args.devices,
    )
    cfg, transport = bundle.config, bundle.transport
    init_state, make_step = bundle.init_state, bundle.make_step
    eval_transport = bundle.eval_transport
    # Batches are staged in the layout the step consumes: peer-sharded
    # over the mesh for ICI, single-device for stacked.
    batch_sharding = bundle.batch_sharding

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.data import (
        device_prefetch,
        load_mnist_or_digits,
        peer_batches,
    )
    from dpwa_tpu.metrics import MetricsLogger
    from dpwa_tpu.train import init_params_per_peer, make_gossip_eval_fn
    from dpwa_tpu.utils.pytree import tree_wire_bytes

    n = cfg.n_peers
    x_tr, y_tr, x_te, y_te, dataset = load_mnist_or_digits()
    model = build_model(x_tr.shape[1:])
    init = lambda k: model.init(k, jnp.zeros((1,) + x_tr.shape[1:]))
    stacked_params = init_params_per_peer(init, jax.random.key(0), n)
    opt = optax.adam(args.lr)
    state = init_state(stacked_params, opt, transport)
    step_fn = make_step(make_loss(model), opt, transport)
    payload = tree_wire_bytes(
        jax.tree.map(lambda v: v[0], state.params),
        cfg.protocol.wire_dtype,
    )

    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    stream = peer_batches(
        x_tr, y_tr, n, args.batch_size, seed=cfg.protocol.seed
    )
    start = 0
    if args.checkpoint:
        # Checkpointing consumes the stream directly (no device_prefetch):
        # prefetch keeps a lookahead of batches in flight, so the stream's
        # saved cursor would run AHEAD of what training actually consumed
        # and a resume would skip those batches.  Exactness beats the
        # copy-overlap here.
        batches = stream
        if args.resume:
            from dpwa_tpu.checkpoint import restore_checkpoint

            state = restore_checkpoint(
                args.checkpoint, like=state, data_stream=stream
            )
            start = int(state.step)
            print(f"resumed at step {start} (batch {stream.batch_count})")
    else:
        batches = device_prefetch(stream, sharding=batch_sharding)
    try:
        for step in range(start, args.steps):
            batch = next(batches)
            if args.checkpoint:
                batch = jax.device_put(batch, batch_sharding)
            state, losses, info = step_fn(state, batch)
            metrics.log_exchange(step, losses, info, payload_bytes=payload)
            if args.checkpoint and (step + 1) % args.save_every == 0:
                from dpwa_tpu.checkpoint import save_checkpoint

                jax.block_until_ready(state.params)
                save_checkpoint(args.checkpoint, state, data_stream=stream)
    finally:
        metrics.close()
    eval_fn = make_gossip_eval_fn(model.apply, eval_transport)
    accs = np.asarray(eval_fn(state.params, jnp.asarray(x_te), jnp.asarray(y_te)))
    print(f"{dataset} per-peer test accuracy: {accs.round(4).tolist()}")
    print(f"mean test accuracy: {accs.mean():.4f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--config",
        default=os.path.join(os.path.dirname(__file__), "nodes.yaml"),
    )
    ap.add_argument("--name", help="this process's node name (TCP transport)")
    ap.add_argument(
        "--transport", choices=("tcp", "ici", "stacked"), default="ici"
    )
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--log-every", type=int, default=25)
    ap.add_argument(
        "--checkpoint", metavar="DIR",
        help="ici/stacked: save full state + data-stream position here "
        "every --save-every steps; with --resume, continue the exact "
        "run (same batches, same exchange sequence)",
    )
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument(
        "--platform", default=None,
        help="TCP mode: pin this worker's jax platform (run_tcp.sh passes "
        "cpu: a chip belongs to one process, so co-hosted workers cannot "
        "all take it); default: the platform jax selects",
    )
    ap.add_argument(
        "--devices", default="auto", choices=("auto", "cpu", "native"),
        help="ici/stacked: 'auto'/'native' run on the platform jax "
        "selected and error when it is short of devices; 'cpu' gives the "
        "emulated host mesh",
    )
    args = ap.parse_args()
    if args.resume and not args.checkpoint:
        ap.error("--resume requires --checkpoint DIR")
    if args.checkpoint and args.transport == "tcp":
        ap.error(
            "--checkpoint is not wired into the per-process tcp loop; use "
            "--transport ici or stacked"
        )
    if args.transport == "tcp":
        if not args.name:
            ap.error("--transport tcp requires --name (this node's identity)")
        run_tcp(args)
    else:
        run_single_process(args, stacked=args.transport == "stacked")


if __name__ == "__main__":
    main()
