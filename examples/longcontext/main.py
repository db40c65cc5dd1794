#!/usr/bin/env python
"""Long-context gossip training: a (peers, sp) 2-D mesh demo.

Each replica's sequences span its ``sp`` sub-axis via exact ring
attention (``dpwa_tpu/ops/ring_attention.py``); replicas gossip over the
``peers`` axis — one ``shard_map`` program per step
(``dpwa_tpu/train_sp.py``).  Runs anywhere with peers*sp devices: a real
slice, or the emulated CPU mesh::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/longcontext/main.py --peers 4 --sp 2

Trains on the synthetic deterministic language the other LM examples use
(no corpus ships with a repo); loss curves are meaningful, steps/sec is a
real end-to-end figure for the 2-D layout.
"""

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
    ap.add_argument("--peers", type=int, default=4)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument(
        "--lora", type=int, default=0, metavar="RANK",
        help="config 5's long-context layout: freeze the base weights, "
        "train rank-RANK LoRA adapters, and gossip ONLY the adapters "
        "over the peers axis (0 = full-weight gossip)",
    )
    ap.add_argument(
        "--sp-layout", choices=("contiguous", "zigzag"),
        default="contiguous",
        help="zigzag balances causal ring attention work across sp "
        "devices (ops/zigzag_ring.py); data is zigzag-sharded here, the "
        "model handles rope positions",
    )
    ap.add_argument(
        "--sp-strategy", choices=("ring", "a2a"), default="ring",
        help="'ring': K/V blocks rotate over the sp axis (flash-kernel "
        "hops); 'a2a': Ulysses all-to-all to head-sharded attention "
        "over the full sequence (ops/ulysses.py)",
    )
    args = ap.parse_args()
    if args.sp_strategy == "a2a" and args.sp_layout == "zigzag":
        raise SystemExit(
            "--sp-layout zigzag balances the causal RING; the a2a "
            "strategy attends over the full sequence and needs the "
            "contiguous layout"
        )

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.utils.devices import ensure_devices

    cfg = make_local_config(args.peers, schedule="ring")
    ensure_devices(args.peers * args.sp)

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.models.llama import Llama, LlamaConfig
    from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.train import init_gossip_state, init_params_per_peer
    from dpwa_tpu.train_sp import (
        make_gossip_sp_train_step,
        make_sp_mesh,
        sp_batch_sharding,
    )

    n, sp, T = args.peers, args.sp, args.seq_len
    div = 2 * sp if args.sp_layout == "zigzag" else sp
    if T % div:
        raise SystemExit(
            f"--seq-len {T} must divide by {div} "
            f"({'2*sp for the zigzag layout' if div != sp else '--sp'})"
        )
    base = dict(
        vocab_size=256,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=8,
        n_kv_heads=4,
        d_ff=args.d_model * 3,
        max_seq_len=T,
        lora_rank=args.lora,
    )
    model = Llama(
        LlamaConfig(
            **base,
            sp_axis="sp",
            sp_layout=args.sp_layout,
            sp_strategy=args.sp_strategy,
        )
    )
    init_model = Llama(LlamaConfig(**base))  # init runs outside shard_map

    mesh = make_sp_mesh(cfg, sp)
    transport = IciTransport(cfg, mesh=mesh)
    stacked = init_params_per_peer(
        lambda k: init_model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0),
        n,
    )
    if args.lora:
        from dpwa_tpu.models.llama import lora_filter, lora_optimizer

        opt = lora_optimizer(
            optax.adam(args.lr), jax.tree.map(lambda v: v[0], stacked)
        )
        exchange_filter = lora_filter
    else:
        opt = optax.adam(args.lr)
        exchange_filter = None
    state = init_gossip_state(stacked, opt, transport)

    def sp_loss(params, batch):
        x, y = batch
        losses = softmax_cross_entropy(model.apply(params, x), y)
        return losses.sum(), jnp.float32(losses.size)

    step_fn = make_gossip_sp_train_step(
        sp_loss, opt, transport, exchange_filter=exchange_filter
    )
    sh = sp_batch_sharding(mesh)

    # Deterministic synthetic language: next token = f(prev) — learnable.
    rng = np.random.default_rng(0)
    table = rng.permutation(256).astype(np.int32)

    def batch():
        starts = rng.integers(1, 256, (n, args.batch_size, 1)).astype(
            np.int32
        )
        toks = [starts]
        for _ in range(T):
            toks.append(table[toks[-1]])
        toks = np.concatenate(toks, axis=-1)
        inputs, targets = toks[..., :-1], toks[..., 1:]
        if args.sp_layout == "zigzag":
            from dpwa_tpu.ops.zigzag_ring import zigzag_shard

            inputs = zigzag_shard(inputs, args.sp, axis=2)
            targets = zigzag_shard(targets, args.sp, axis=2)
        return (
            jax.device_put(inputs, sh),
            jax.device_put(targets, sh),
        )

    state, losses, info = step_fn(state, batch())
    jax.block_until_ready((state, losses))
    t0 = time.perf_counter()
    for step in range(1, args.steps):
        state, losses, info = step_fn(state, batch())
        if step % args.log_every == 0:
            print(
                f"step {step}: loss/peer "
                f"{np.round(np.asarray(losses), 3).tolist()} "
                f"partners {np.asarray(info.partner).tolist()}"
            )
    jax.block_until_ready((state, losses))
    dt = time.perf_counter() - t0
    print(
        f"peers={n} x sp={sp} (T={T}): "
        f"{(args.steps - 1) / dt:.3f} steps/sec, final mean loss "
        f"{float(np.asarray(losses).mean()):.4f}"
    )


if __name__ == "__main__":
    main()
