#!/usr/bin/env python
"""Per-hop ring-attention compute: Pallas flash hop vs q-chunked einsum hop.

VERDICT r3 weak #2's done-criterion: on-chip per-hop timing at long T
showing the flash-ring hop (ops/flash_ring.py) at-or-near the
single-device flash kernel's throughput, against the q-chunked einsum
hop it replaces (ops/ring_attention.py's xla path).

What one chip CAN measure honestly: the HOP — the unit of work each sp
device runs per ring step — at realistic per-device block lengths.  A
hop is (Q block × held K/V block) attention; with sp devices and global
sequence T_global, T_local = T_global / sp, and the sp path runs sp such
hops per device per step.  So hop time at T_local IS the sp path's
per-device compute profile; only the ppermute overlap needs real
multi-chip fabric.

Measured per T_local ∈ {8192, 16384, 32768} (Llama-block dims: H=8,
D=128, bf16, B=1; ~131k global at sp=4–16):

- fwd hop:   flash (`_hop_fwd_pallas`) vs einsum (`hop_attn` q-chunked)
- fwd+bwd:   flash custom-vjp hop (`ring_flash_attention_local` on a
             1-device mesh — n=1 ring ≡ exactly one diagonal-causal hop)
             vs the xla ring on the same 1-device mesh

→ merged under key "flash_ring_hop_timing" into
artifacts/attention_memory.json (the long-context artifact of record).

Run on the chip; off-TPU it refuses rather than record CPU numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

B, H, D = 1, 8, 128  # attention_memory.py's Llama-block head layout
T_LOCALS = (8192, 16384, 32768)


def log(m):
    print(m, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--t-locals", type=int, nargs="*", default=list(T_LOCALS)
    )
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="(tests only) run tiny shapes on the CPU backend",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from dpwa_tpu.ops.flash_ring import ring_flash_attention_local
    from dpwa_tpu.ops.ring_attention import ring_attention
    from dpwa_tpu.utils.profiling import timed_loop

    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        log(f"backend is {backend!r}, not tpu — refusing to record "
            "(pass --allow-cpu for a smoke run)")
        sys.exit(3)

    log(f"backend {backend}")
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    rows = []
    for T in args.t_locals:
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (
            jax.random.normal(kk, (B, T, H, D), jnp.bfloat16) for kk in ks
        )
        results = {"t_local": T}
        for name, impl in (("flash", "auto"), ("einsum", "xla")):
            # n=1 ring: exactly one diagonal-causal hop — the per-hop
            # unit, with identical surrounding machinery for both paths.
            def fwd(c, step, impl=impl):
                return ring_attention(q, k, v, mesh, impl=impl)

            try:
                t_fwd, _ = timed_loop(
                    fwd, fwd(None, 0), args.iters, warmup=2,
                )

                def loss(q, impl=impl):
                    return (
                        ring_attention(q, k, v, mesh, impl=impl)
                        .astype(jnp.float32) ** 2
                    ).mean()

                grad = jax.jit(jax.grad(loss))

                t_bwd, _ = timed_loop(
                    lambda c, step: grad(q),
                    grad(q),
                    max(2, args.iters // 2), warmup=1,
                )
                results[name] = {
                    "fwd_ms": round(float(t_fwd) * 1e3, 3),
                    "fwdbwd_ms": round(float(t_bwd) * 1e3, 3),
                }
                log(f"T={T} {name}: fwd {float(t_fwd)*1e3:.1f} ms, "
                    f"fwd+bwd {float(t_bwd)*1e3:.1f} ms")
            except Exception as e:  # OOM at the largest T is a result
                results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                log(f"T={T} {name}: {type(e).__name__}")
        fl, ei = results.get("flash", {}), results.get("einsum", {})
        # Ratios only where both sides measured (an OOM row has no time).
        if fl.get("fwd_ms", 0) > 0 and "fwd_ms" in ei:
            results["flash_speedup_fwd"] = round(
                ei["fwd_ms"] / fl["fwd_ms"], 2
            )
        if fl.get("fwdbwd_ms", 0) > 0 and "fwdbwd_ms" in ei:
            results["flash_speedup_fwdbwd"] = round(
                ei["fwdbwd_ms"] / fl["fwdbwd_ms"], 2
            )
        rows.append(results)

    path = os.path.join(REPO, "artifacts", "attention_memory.json")
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    import datetime

    data["flash_ring_hop_timing"] = {
        "backend": backend,
        "captured_at_utc": datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%SZ"),
        "dims": f"B={B}, H={H}, D={D}, bf16, diagonal-causal hop",
        "note": (
            "per-hop unit of the sp ring path (n=1 ring == one hop); "
            "T_local = T_global / sp, sp hops per device per step"
        ),
        "rows": rows,
    }
    with open(path + ".tmp", "w") as f:
        json.dump(data, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps(data["flash_ring_hop_timing"], indent=1))


if __name__ == "__main__":
    main()
