#!/usr/bin/env python
"""Llama LoRA fine-tune with subset-pytree gossip — BASELINE config 5.

BASELINE.json:11: "Llama-3-8B LoRA fine-tune, pairwise-avg of LoRA adapters
across v5p-128".  Base weights are hard-frozen and NEVER enter the exchange;
only the LoRA adapter factors (a few MB) gossip — so the per-step collective
cost is independent of the 8B base model.

``--full-size`` instantiates the real Llama-3-8B dims (needs the HBM of a
real slice); the default is a small config with identical pytree paths and
exchange semantics.  Training data is a synthetic deterministic language
(no corpus ships with a repo)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def certify(args) -> int:
    """Chaos-certify this config's exchange regime over the REAL
    multi-process TCP stack (docs/training.md): the harness's LoRA leg
    trains an adapter-only pytree at d≈100K — the same ~400 KB frame
    class this example gossips — through transport, trust, and obs,
    and judges convergence, exchange, and incident silence."""
    import tempfile

    from dpwa_tpu.run.legs import lora_leg
    from dpwa_tpu.run.report import render_report

    workdir = tempfile.mkdtemp(prefix="dpwa-lora-certify-")
    res = lora_leg(
        workdir, n_peers=args.certify_peers, base_port=args.certify_port
    )
    print(render_report(res.report))
    print(
        f"lora certify: {'ok' if res.ok else 'FAILED'} "
        + json.dumps(res.verdict, default=str)
    )
    return 0 if res.ok else 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--peers", type=int, default=8)
    ap.add_argument("--lora-rank", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full-size", action="store_true",
                    help="real Llama-3-8B dims (needs real HBM)")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--certify", action="store_true",
                    help="run the chaos-certification LoRA leg "
                    "(dpwa_tpu/run/, adapter-only exchange over the "
                    "real TCP stack) instead of the SPMD timing loop")
    ap.add_argument("--certify-peers", type=int, default=4,
                    help="peer count for --certify")
    ap.add_argument("--certify-port", type=int, default=47300,
                    help="base TCP port for --certify")
    from dpwa_tpu.utils.launch import add_transport_args, build_transport

    add_transport_args(ap)
    args = ap.parse_args()
    if args.certify:
        sys.exit(certify(args))

    from dpwa_tpu.config import make_local_config

    cfg = make_local_config(args.peers, schedule="random", pool_size=16)
    bundle = build_transport(
        cfg, args.transport, args.devices, wire_dtype=args.wire_dtype
    )
    cfg = bundle.config  # effective config (wire_dtype applied)
    transport = bundle.transport

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.metrics import MetricsLogger
    from dpwa_tpu.models.llama import (
        Llama,
        LlamaConfig,
        llama3_8b_config,
        lora_filter,
        lora_optimizer,
    )
    from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.pytree import (
        partition,
        tree_size_bytes,
        tree_wire_bytes,
    )

    n = cfg.n_peers
    if args.full_size:
        mcfg = llama3_8b_config(lora_rank=args.lora_rank)
    else:
        mcfg = LlamaConfig(
            vocab_size=256, d_model=64, n_layers=4, n_heads=8, n_kv_heads=4,
            d_ff=128, max_seq_len=args.seq_len, lora_rank=args.lora_rank,
        )
    model = Llama(mcfg)
    tokens0 = jnp.zeros((1, args.seq_len), jnp.int32)
    init = lambda k: model.init(k, tokens0)
    stacked = init_params_per_peer(init, jax.random.key(0), n)
    opt = lora_optimizer(
        optax.adam(args.lr), jax.tree.map(lambda v: v[0], stacked)
    )
    state = bundle.init_state(stacked, opt, transport)

    def loss_fn(params, batch):
        tokens, targets = batch
        logits = model.apply(params, tokens)
        return softmax_cross_entropy(logits, targets).mean()

    step_fn = bundle.make_step(
        loss_fn, opt, transport, exchange_filter=lora_filter
    )
    one = jax.tree.map(lambda v: v[0], state.params)
    lora_sel, _ = partition(one, lora_filter)
    total = tree_size_bytes(one)
    lora_bytes = tree_wire_bytes(
        {i: l for i, l in enumerate(jax.tree.leaves(lora_sel))},
        cfg.protocol.wire_dtype,
    )
    print(
        f"Llama {'3-8B' if args.full_size else 'tiny'} x{n} peers; "
        f"model {total/1e6:.1f} MB, gossiped LoRA payload "
        f"{lora_bytes/1e6:.3f} MB/exchange",
        file=sys.stderr,
    )

    rng = np.random.default_rng(0)
    V = mcfg.vocab_size

    def batch():
        starts = rng.integers(1, V, (n, args.batch_size, 1))
        seq = [starts]
        for _ in range(args.seq_len):
            seq.append((3 * seq[-1] + 1) % V)
        toks = np.concatenate(seq, axis=-1).astype(np.int32)
        # Straight from numpy to the layout the step consumes (peer-sharded
        # on a mesh, the one device when stacked).
        return jax.device_put(
            (toks[..., :-1], toks[..., 1:]), bundle.batch_sharding
        )

    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    state, losses, info = step_fn(state, batch())
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    try:
        for step in range(1, args.steps):
            state, losses, info = step_fn(state, batch())
            metrics.log_exchange(step, losses, info, payload_bytes=lora_bytes)
    finally:
        metrics.close()
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    plat = jax.devices()[0].platform
    ndev = 1 if args.transport == "stacked" else n
    print(
        f"steps/sec (all {n} peers, incl. exchange, on {plat} x{ndev}): "
        f"{(args.steps-1)/dt:.3f}"
    )


if __name__ == "__main__":
    main()
