#!/usr/bin/env python
"""Max sequence length per device: dense vs flash vs ring-remat attention.

VERDICT r2 item 5: make ring attention viable at real sequence lengths and
MEASURE the ceiling.  This experiment probes, on the real chip, the longest
sequence a single device can train (fwd+bwd) through one Llama block
(d_model 1024, 8 heads x 128, SwiGLU d_ff 2816, bf16) under three
attention implementations:

- ``dense``  — the O(T^2) einsum path (materializes [B,H,T,T] f32 scores);
- ``flash``  — the Pallas TPU flash kernel (scores live in VMEM tiles);
- ``ring``   — ``ring_attention_local`` on a 1-device sp mesh with the
  flash-style q-chunk + remat hop (the per-device memory profile of the
  sequence-parallel path: what each device of an sp group pays).

Each (impl, T) probe runs in its own subprocess: an OOM kills only the
probe, and the allocator starts clean every time.  Results →
``artifacts/attention_memory.json``.

Usage: python experiments/attention_memory.py            # full sweep
       python experiments/attention_memory.py --probe dense 8192  # internal
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

D_MODEL, N_HEADS, D_FF = 1024, 8, 2816
B = 1
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_block(impl: str):
    import jax
    import jax.numpy as jnp

    from dpwa_tpu.models.llama import Block, LlamaConfig

    cfg = dict(
        vocab_size=256,
        d_model=D_MODEL,
        n_layers=1,
        n_heads=N_HEADS,
        d_ff=D_FF,
        max_seq_len=1 << 22,
        dtype=jnp.bfloat16,
    )
    if impl == "ring":
        return Block(LlamaConfig(**cfg, sp_axis="sp"))
    return Block(LlamaConfig(**cfg, attn_impl=impl))


def probe(impl: str, T: int, iters: int) -> float:
    """One block fwd+bwd at sequence length T; returns seconds/step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dpwa_tpu.utils.profiling import timed_loop

    block = build_block(impl)
    x = jax.random.normal(
        jax.random.key(0), (B, T, D_MODEL), jnp.bfloat16
    )
    positions = jnp.arange(T)
    params = None

    if impl == "ring":
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

        # Init with the non-sp twin (outside shard_map), tiny T.
        init_block = build_block("dense")
        params = init_block.init(
            jax.random.key(1), x[:, :128], positions[:128]
        )

        def loss(params, x):
            def body(p, xx):
                out = block.apply(p, xx, jnp.arange(xx.shape[1]))
                return jnp.sum(out.astype(jnp.float32) ** 2)[None]

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(None, "sp", None)),
                out_specs=P("sp"),
            )(params, x).sum()

    else:
        params = block.init(jax.random.key(1), x[:, :128], positions[:128])

        def loss(params, x):
            out = block.apply(params, x, positions)
            return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.grad(loss))
    per_iter, _ = timed_loop(
        lambda g, k: grad_fn(params, x),
        grad_fn(params, x),
        iters,
        warmup=1,
    )
    return float(per_iter)


def run_probe(impl: str, T: int, timeout_s: float, iters: int = 25) -> dict:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--probe", impl, str(T),
        "--iters", str(iters),
    ]
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            env=os.environ.copy(), cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return {"T": T, "ok": False, "why": f"timeout>{timeout_s:.0f}s"}
    for line in proc.stdout.splitlines():
        if line.startswith("SECONDS "):
            return {
                "T": T,
                "ok": True,
                "seconds_per_step": float(line.split()[1]),
                "wall": round(time.time() - t0, 1),
            }
    why = (proc.stderr or "").strip().splitlines()
    oom = any(
        "RESOURCE_EXHAUSTED" in l
        or "Out of memory" in l
        or "Ran out of memory" in l
        or "would exceed memory" in l
        for l in why
    )
    detail = next(
        (
            l
            for l in reversed(why)
            if ("Error" in l or "error:" in l) and "TRACEBACK" not in l.upper()
            and "internal frames" not in l
        ),
        why[-1] if why else f"rc={proc.returncode}",
    )
    return {"T": T, "ok": False, "why": "oom" if oom else detail[:200]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", nargs=2, metavar=("IMPL", "T"))
    ap.add_argument("--iters", type=int, default=25)
    ap.add_argument("--start", type=int, default=4096)
    ap.add_argument("--max-t", type=int, default=1 << 18)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "artifacts", "attention_memory.json")
    )
    args = ap.parse_args()

    if args.probe:
        impl, T = args.probe[0], int(args.probe[1])
        print(f"SECONDS {probe(impl, T, args.iters):.6f}", flush=True)
        return

    import jax  # noqa: F401 — only to record the backend in the artifact

    results = {}
    for impl in ("dense", "flash", "ring"):
        rows, T = [], args.start
        while T <= args.max_t:
            row = run_probe(impl, T, args.timeout, args.iters)
            rows.append(row)
            print(f"{impl} T={T}: {row}", file=sys.stderr, flush=True)
            if not row["ok"]:
                break
            T *= 2
        max_ok = max((r["T"] for r in rows if r["ok"]), default=0)
        results[impl] = {"max_T": max_ok, "probes": rows}

    import jax

    out = {
        "experiment": "attention_memory",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "block": {
            "d_model": D_MODEL, "n_heads": N_HEADS, "d_ff": D_FF,
            "dtype": "bfloat16", "batch": B,
        },
        "note": (
            "max trainable (fwd+bwd) sequence length through ONE Llama "
            "block on a single device; ring = per-device profile of the "
            "sp path (q-chunk 256 + remat), probed at sp=1"
        ),
        "results": results,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v["max_T"] for k, v in results.items()}))


if __name__ == "__main__":
    main()
