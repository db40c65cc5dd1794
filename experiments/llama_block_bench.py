#!/usr/bin/env python
"""Config 5 at REAL dimensions on the chip: one Llama-3-8B block + LoRA
exchange.

VERDICT r2 item 6 (BASELINE.json:11 — "Llama-3-8B LoRA fine-tune,
pairwise-avg of LoRA adapters").  The FULL 8B model cannot fit this box:
32 layers x ~218M params ~= 14.6 GB in bf16 before gradients, optimizer
state, or activations — past the single v5e core's 16 GB HBM.  What CAN
be measured honestly at real scale, and is here:

1. ONE transformer block at the exact Llama-3-8B dimensions (d_model
   4096, 32 heads x 128, 8 KV heads, SwiGLU d_ff 14336, bf16, LoRA rank
   16) — fwd and fwd+bwd wall time at the model's native 8192-token
   context (Pallas flash attention path).
2. The LoRA-subset gossip exchange at FULL-model scale: the flat adapter
   vector for all 32 layers (rank 16 -> ~42M params) pairwise-merged
   across 8 stacked virtual peers on-chip — the exact payload config 5
   ships per gossip round, with bytes and GB/s.

Results -> artifacts/llama_block_real_dims.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_PEERS = 8
B = 1
LORA_RANK = 16


def lora_params_per_block(cfg) -> int:
    d, kv_d, ff, r = (
        cfg.d_model,
        cfg.kv_heads * cfg.head_dim,
        cfg.d_ff,
        cfg.lora_rank,
    )
    sizes = [
        (d, d),  # wq
        (d, kv_d),  # wk
        (d, kv_d),  # wv
        (d, d),  # wo
        (d, ff),  # w_gate
        (d, ff),  # w_up
        (ff, d),  # w_down
    ]
    return sum(r * (i + o) for i, o in sizes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--seq-len", type=int, default=8192,
        help="tokens per block step (8192 = the model's native context)",
    )
    args = ap.parse_args()
    T = args.seq_len

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if jax.default_backend() != "tpu":
        # A CPU run must never write a number under the real-dims
        # artifact name BASELINE.md cites.
        print(
            f"refusing to run: backend is {jax.default_backend()!r}, not "
            "the chip",
            file=sys.stderr,
        )
        sys.exit(3)

    from dpwa_tpu.models.llama import (
        Block,
        LlamaConfig,
        llama3_8b_config,
        lora_optimizer,
    )
    from dpwa_tpu.utils.profiling import timed_loop

    full = llama3_8b_config(lora_rank=LORA_RANK)
    cfg = LlamaConfig(
        vocab_size=full.vocab_size,
        d_model=full.d_model,
        n_layers=1,
        n_heads=full.n_heads,
        n_kv_heads=full.n_kv_heads,
        d_ff=full.d_ff,
        max_seq_len=T,
        rope_theta=full.rope_theta,
        lora_rank=full.lora_rank,
        dtype=jnp.bfloat16,
    )
    log = lambda m: print(m, file=sys.stderr, flush=True)
    block = Block(cfg)
    x = jax.random.normal(jax.random.key(0), (B, T, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(T)
    log("init block params ...")
    params = block.init(jax.random.key(1), x[:, :128], positions[:128])
    n_params = sum(v.size for v in jax.tree.leaves(params))
    log(f"params: {n_params/1e6:.1f}M; compiling fwd @ T={T} ...")

    # --- 1a. block forward -------------------------------------------------
    fwd = jax.jit(lambda p, x: block.apply(p, x, positions))
    t_fwd, _ = timed_loop(
        lambda c, k: fwd(params, x),
        fwd(params, x),
        20,
        warmup=2,
    )

    log(f"fwd {float(t_fwd)*1e3:.2f} ms; compiling train step ...")
    # --- 1b. block fwd+bwd (LoRA-only training, base frozen) ---------------
    opt = lora_optimizer(optax.adam(1e-4), params)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, x):
        def loss(p):
            out = block.apply(p, x, positions)
            return jnp.mean(out.astype(jnp.float32) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    carry = train_step(params, opt_state, x)
    t_step, _ = timed_loop(
        lambda c, k: train_step(c[0], c[1], x),
        carry,
        20,
        warmup=1,
    )

    log(f"train step {float(t_step)*1e3:.2f} ms; LoRA exchange bench ...")
    # --- 2. LoRA exchange at full-model scale ------------------------------
    per_block = lora_params_per_block(cfg)
    lora_total = per_block * full.n_layers
    from dpwa_tpu.ops.merge import involution_pairs, pallas_pair_merge
    from dpwa_tpu.parallel.schedules import _ring_even, _ring_odd

    d_vec = (lora_total + 1023) // 1024 * 1024  # pad to the kernel tile
    pools = [_ring_even(N_PEERS), _ring_odd(N_PEERS)]
    n_pairs = max(len(involution_pairs(p)[0]) for p in pools)
    lr = [involution_pairs(p, pad_to=n_pairs) for p in pools]
    lefts = [jnp.asarray(l) for l, _ in lr]
    rights = [jnp.asarray(r) for _, r in lr]
    alphas = jnp.full((N_PEERS,), 0.5, jnp.float32)
    vec = (
        jnp.ones((N_PEERS, d_vec // 128, 128), jnp.float32)
        * jnp.arange(N_PEERS, dtype=jnp.float32)[:, None, None]
    )
    t_exch, _ = timed_loop(
        lambda b, k: pallas_pair_merge(
            b, lefts[k % 2], rights[k % 2], alphas
        ),
        vec,
        50,
        warmup=2,
    )
    actual_pairs = min(len(involution_pairs(p)[0]) for p in pools)
    bytes_per_round = 2 * 2 * actual_pairs * d_vec * 4  # rd+wr per member

    out = {
        "experiment": "llama3_8b_block_real_dims",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "note": (
            "full 8B does NOT fit one 16GB v5e core (32 x ~218M params "
            "~14.6GB bf16 before grads/opt/activations); measured instead: "
            "one block at exact dims + the full-model LoRA exchange payload"
        ),
        "block": {
            "dims": (
                f"d_model {cfg.d_model}, heads {cfg.n_heads}x"
                f"{cfg.head_dim}, kv {cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
                "bf16"
            ),
            "lora_rank": LORA_RANK,
            "params": int(n_params),
            "seq_len": T,
            "batch": B,
            "fwd_ms": round(float(t_fwd) * 1e3, 3),
            "train_step_ms": round(float(t_step) * 1e3, 3),
            "tokens_per_sec_fwd": round(B * T / float(t_fwd), 1),
            "est_32layer_fwd_ms": round(32 * float(t_fwd) * 1e3, 1),
        },
        "lora_exchange": {
            "n_peers": N_PEERS,
            "lora_params_per_block": int(per_block),
            "lora_params_full_model": int(lora_total),
            "payload_mb_per_peer": round(lora_total * 4 / 1e6, 2),
            "round_ms": round(float(t_exch) * 1e3, 3),
            "gbps_per_chip": round(
                bytes_per_round / float(t_exch) / N_PEERS / 1e9, 2
            ),
            "note": (
                "8 stacked virtual peers on one chip, ring pairing, "
                "in-place Pallas pair-merge kernel; payload = all 32 "
                "layers' adapters (f32 wire)"
            ),
        },
    }
    path = os.path.join(REPO, "artifacts", "llama_block_real_dims.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
