#!/usr/bin/env python
"""ImageNet ResNet-50, 32-peer random-pair gossip — BASELINE config 3.

BASELINE.json:9: "ImageNet ResNet-50, 32-peer random-pair schedule (v4-32,
ppermute)".  Each peer trains ResNet-50 on its own shard; every step a fresh
random perfect matching (drawn from the compiled pairing pool) pairs the
peers for the exchange.

ImageNet itself can't ship with a repo (and this box has no egress), so
this example trains on ImageNet-shaped synthetic data (``--synthetic``,
implied): the model, schedule, and collective are all real, and steps/sec
is a true training-system throughput.  Wire a real loader through
``dpwa_tpu.data.peer_batches`` + ``device_prefetch`` when a dataset
directory exists."""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--peers", type=int, default=32)
    ap.add_argument("--config", help="optional YAML (overrides --peers)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument(
        "--synthetic", action="store_true",
        help="(implied) train on ImageNet-shaped synthetic data; this "
        "example has no real-data loader — wire one through "
        "dpwa_tpu.data.peer_batches when a dataset directory exists",
    )
    ap.add_argument("--log-every", type=int, default=20)
    from dpwa_tpu.utils.launch import add_transport_args, build_transport

    add_transport_args(ap)
    args = ap.parse_args()

    from dpwa_tpu.config import load_config, make_local_config

    if args.config:
        cfg = load_config(args.config)
    else:
        # Programmatic equivalent of a 32-node YAML (same schema).
        cfg = make_local_config(args.peers, schedule="random", pool_size=32)
    bundle = build_transport(
        cfg, args.transport, args.devices, wire_dtype=args.wire_dtype
    )
    cfg = bundle.config  # effective config (wire_dtype applied)
    transport = bundle.transport

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.metrics import MetricsLogger
    from dpwa_tpu.models.resnet import ResNet50
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.pytree import tree_wire_bytes

    n = cfg.n_peers
    S = args.image_size
    model = ResNet50(dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    init = lambda k: model.init(k, jnp.zeros((1, S, S, 3)))
    stacked = init_params_per_peer(init, jax.random.key(0), n)
    opt = optax.sgd(args.lr, momentum=0.9)
    state = bundle.init_state(stacked, opt, transport)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    step_fn = bundle.make_step(loss_fn, opt, transport)
    payload = tree_wire_bytes(
        jax.tree.map(lambda v: v[0], state.params),
        cfg.protocol.wire_dtype,
    )
    print(
        f"ResNet-50 x{n} peers, payload {payload/1e6:.1f} MB/exchange, "
        f"random-pair pool of {transport.schedule.pool_size}",
        file=sys.stderr,
    )

    rng = np.random.default_rng(0)

    # Synthetic batches are pre-staged on device and cycled: regenerating
    # n*batch*S*S*3 floats in numpy (hundreds of MB at the 32-peer
    # default) and shipping them host→device EVERY step measures the host
    # RNG and the transfer link, not the training system.  Two distinct
    # batches keep XLA from constant-folding while the steps/sec figure
    # measures compute + exchange, which is the point of synthetic data.
    # device_put of the raw numpy goes straight to the target sharding —
    # no default-device staging copy.
    pool = []
    for _ in range(2):
        x = rng.random((n, args.batch_size, S, S, 3), np.float32)
        y = rng.integers(0, 1000, (n, args.batch_size)).astype(np.int32)
        pool.append(
            (
                jax.device_put(x, bundle.batch_sharding),
                jax.device_put(y, bundle.batch_sharding),
            )
        )

    def batch(step):
        return pool[step % len(pool)]

    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    state, losses, info = step_fn(state, batch(0))
    jax.block_until_ready((state, losses))
    t0 = time.perf_counter()
    try:
        for step in range(1, args.steps):
            state, losses, info = step_fn(state, batch(step))
            metrics.log_exchange(step, losses, info, payload_bytes=payload)
    finally:
        metrics.close()
    jax.block_until_ready((state, losses))
    dt = time.perf_counter() - t0
    plat = jax.devices()[0].platform
    ndev = 1 if args.transport == "stacked" else n
    print(
        f"steps/sec (all {n} peers, incl. exchange, on {plat} x{ndev}): "
        f"{(args.steps-1)/dt:.3f}"
    )


if __name__ == "__main__":
    main()
