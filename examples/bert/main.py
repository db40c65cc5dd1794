#!/usr/bin/env python
"""BERT-base MLM with hierarchical intra/inter-host gossip — config 4.

BASELINE.json:10: "BERT-base MLM (Flax), 64-peer gossip, hierarchical
intra/inter-host averaging".  Peers form groups of ``--group-size`` (chips
per host); most steps gossip inside the group over ICI, every
``--inter-period``-th step pairs peers across groups over DCN.

With no corpus on disk this trains on a synthetic deterministic language
(next token = f(previous)), which MLM genuinely learns — loss curves are
meaningful, wall-clock numbers are real."""

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
    """Chaos-certify the image-class training regime over the REAL
    multi-process TCP stack (docs/training.md): the harness's clean leg
    trains the MNIST-class digits model per peer and judges gossip
    time-to-loss against a single-process SGD control arm at equal
    total steps, with the incident plane required silent."""
    import tempfile

    from dpwa_tpu.run.legs import clean_leg
    from dpwa_tpu.run.report import render_report

    workdir = tempfile.mkdtemp(prefix="dpwa-bert-certify-")
    res = clean_leg(
        workdir, n_peers=args.certify_peers, base_port=args.certify_port
    )
    print(render_report(res.report))
    print(
        f"clean certify: {'ok' if res.ok else 'FAILED'} "
        + json.dumps(res.verdict, default=str)
    )
    return 0 if res.ok else 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--peers", type=int, default=64)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--inter-period", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--tiny", action="store_true", help="tiny BERT (tests)")
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute "
                    "(the dtype the TPU's peak is quoted in)")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--certify", action="store_true",
                    help="run the chaos-certification clean leg "
                    "(dpwa_tpu/run/, gossip vs single-process SGD "
                    "time-to-loss over the real TCP stack) instead of "
                    "the SPMD timing loop")
    ap.add_argument("--certify-peers", type=int, default=8,
                    help="peer count for --certify")
    ap.add_argument("--certify-port", type=int, default=47200,
                    help="base TCP port for --certify")
    from dpwa_tpu.utils.launch import add_transport_args, build_transport

    add_transport_args(ap)
    args = ap.parse_args()
    if args.certify:
        sys.exit(certify(args))

    from dpwa_tpu.config import make_local_config

    cfg = make_local_config(
        args.peers,
        schedule="hierarchical",
        group_size=args.group_size,
        inter_period=args.inter_period,
    )
    bundle = build_transport(
        cfg, args.transport, args.devices, wire_dtype=args.wire_dtype
    )
    cfg = bundle.config  # effective config (wire_dtype applied)
    transport = bundle.transport

    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.metrics import MetricsLogger
    from dpwa_tpu.models.bert import (
        BertMLM,
        bert_base_config,
        bert_tiny_config,
        mlm_loss_fn,
        mlm_mask_batch,
    )
    from dpwa_tpu.train import stack_params
    from dpwa_tpu.utils.pytree import tree_wire_bytes

    n = cfg.n_peers
    dtype = jnp.bfloat16 if args.bf16 else None
    mcfg = bert_tiny_config(dtype) if args.tiny else bert_base_config(dtype)
    if args.seq_len > mcfg.max_seq_len:
        hint = " (tiny BERT is 64)" if args.tiny else ""
        ap.error(
            f"--seq-len {args.seq_len} exceeds the model's max_seq_len "
            f"{mcfg.max_seq_len}{hint}; pass --seq-len "
            f"{mcfg.max_seq_len} or less"
        )
    model = BertMLM(mcfg)
    tokens0 = jnp.zeros((1, args.seq_len), jnp.int32)
    stacked = stack_params(model.init(jax.random.key(0), tokens0), n)
    opt = optax.adamw(args.lr)
    state = bundle.init_state(stacked, opt, transport)
    step_fn = bundle.make_step(mlm_loss_fn(model), opt, transport)
    payload = tree_wire_bytes(
        jax.tree.map(lambda v: v[0], state.params),
        cfg.protocol.wire_dtype,
    )
    print(
        f"BERT {'tiny' if args.tiny else 'base'} x{n} peers "
        f"({n // args.group_size} groups), payload {payload/1e6:.1f} MB",
        file=sys.stderr,
    )

    rng = np.random.default_rng(0)
    V = mcfg.vocab_size

    def batch():
        starts = rng.integers(1, V, (n, args.batch_size, 1))
        seq = [starts]
        for _ in range(args.seq_len - 1):
            seq.append((2 * seq[-1] + 1) % V)
        tokens = np.concatenate(seq, axis=-1)
        # Straight from numpy to the layout the step consumes (peer-sharded
        # on a mesh, the one device when stacked).
        return jax.device_put(
            mlm_mask_batch(tokens, rng), bundle.batch_sharding
        )

    metrics = MetricsLogger(stream=sys.stdout, every=args.log_every)
    state, losses, info = step_fn(state, batch())
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    try:
        for step in range(1, args.steps):
            state, losses, info = step_fn(state, batch())
            metrics.log_exchange(step, losses, info, payload_bytes=payload)
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
