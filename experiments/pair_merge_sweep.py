"""Tuning sweep for the in-place pair-merge kernel's DMA pipeline.

The headline bench (bench.py) runs `pallas_pair_merge` with its default
``r_block=1024, n_buf=2``.  This sweep measures the achieved GB/s/chip over
the (r_block, n_buf) grid at the benchmark payload, so the defaults can be
set to whatever actually saturates the chip the driver benches on, instead
of whatever was guessed first.  Accounting matches bench.py exactly
(2 HBM ops per merged row, actual pairs only).

Run on the TPU chip:  python experiments/pair_merge_sweep.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size", type=int, default=24 * 1024 * 1024)
    ap.add_argument("--peers", type=int, default=8)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--r-blocks", default="512,1024,2048,4096,8192")
    ap.add_argument("--n-bufs", default="2,3,4")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dpwa_tpu.ops.merge import involution_pairs, pallas_pair_merge
    from dpwa_tpu.parallel.schedules import _ring_even, _ring_odd
    from dpwa_tpu.utils.profiling import timed_loop

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    on_tpu = jax.default_backend() == "tpu"
    n, d = args.peers, args.size
    pools = [_ring_even(n), _ring_odd(n)]
    actual_pairs = [len(involution_pairs(p)[0]) for p in pools]
    n_pairs = max(actual_pairs)
    lr = [involution_pairs(p, pad_to=n_pairs) for p in pools]
    lefts = [jnp.asarray(l) for l, _ in lr]
    rights = [jnp.asarray(r) for _, r in lr]
    alphas = jnp.full((n,), 0.5, jnp.float32)

    results = []
    for r_block in [int(x) for x in args.r_blocks.split(",")]:
        for n_buf in [int(x) for x in args.n_bufs.split(",")]:
            # VMEM: n_buf * 2 rows * r_block * 128 lanes * 4 B, in + out.
            vmem_mb = n_buf * 2 * r_block * 128 * 4 * 2 / 1e6
            if vmem_mb > 100:
                continue
            x = jnp.ones((n, d // 128, 128), jnp.float32)
            try:
                per_iter, _ = timed_loop(
                    lambda b, step: pallas_pair_merge(
                        b, lefts[step % 2], rights[step % 2], alphas,
                        r_block=r_block, n_buf=n_buf, interpret=not on_tpu,
                    ),
                    x,
                    args.iters,
                    warmup=2,
                )
            except Exception as e:  # noqa: BLE001 - report and keep sweeping
                print(f"r_block={r_block} n_buf={n_buf}: FAILED {e}")
                continue
            total_bytes = sum(
                2 * actual_pairs[s % 2] * 2 * d * 4
                for s in range(args.iters)
            )
            gbps = total_bytes / (per_iter * args.iters) / 1e9
            results.append(
                {"r_block": r_block, "n_buf": n_buf,
                 "vmem_mb": round(vmem_mb, 1), "gbps": round(gbps, 2)}
            )
            print(f"r_block={r_block:5d} n_buf={n_buf}: {gbps:7.2f} GB/s "
                  f"({vmem_mb:.1f} MB VMEM)")
    results.sort(key=lambda r: -r["gbps"])
    print(json.dumps({"best": results[0] if results else None,
                      "all": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
