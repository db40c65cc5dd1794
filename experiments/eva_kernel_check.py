#!/usr/bin/env python3
"""The EVA core's kernels against their plain twin on the chip, and what one
layer's calls cost.

    chiprun -- python experiments/eva_kernel_check.py [--time] [--blocks 256 512]

Needs a TPU (exits 4 without one).  At the published head size (32 heads of
128, window 2,048, chunk 16) and T 4,096 (two windows: the second sees the
first's 128 summaries) it compares ``ops/eva.kernel_eva_attention`` with
``ops/eva.plain_eva_attention`` in value and in the five gradients (q, k, v,
ksum, vsum), the twin under ``jax.default_matmul_precision("highest")``.

Tolerance: :data:`TOLERANCE` of the largest value of what is compared.  Both
sides read the same bfloat16 inputs; the twin keeps everything after them in
float32, the kernels round the probabilities and ``ds`` to bfloat16 where they
enter a matmul (``mixedp_attn``: 2^-9 a value, summed over hundreds of keys
with random signs) and hand back bfloat16 results (2^-9 of each result): a
few times 1e-3 is honest, 1e-2 is a lost term.

``--time`` also times one layer's calls at the cell's shapes (2 x 32 x 16,384
x 128, the peer axis folded): forward, and forward + backward, in ms, for each
``--blocks`` value of ``ops/eva.sub_block`` (default: the function's own).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOLERANCE = 1e-2
NAMES = ("q", "k", "v", "ksum", "vsum")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time", action="store_true")
    ap.add_argument("--blocks", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("eva_kernel_check.py needs a TPU", file=sys.stderr)
        return 4
    from dpwa_tpu.ops import eva
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    window, chunk, heads, d = 2048, 16, 32, 128

    def inputs(seed, batch, steps):
        keys = jax.random.split(jax.random.key(seed), 6)
        shape = (batch, heads, steps, d)
        q, k, v = (
            jax.random.normal(key, shape, jnp.bfloat16) for key in keys[:3]
        )
        phi, mu = (
            jax.random.normal(key, (heads, d), jnp.bfloat16) * d ** -0.5
            for key in keys[3:5]
        )
        ksum, vsum = eva.chunk_summaries(k, v, phi, mu, chunk)
        weights = jax.random.normal(keys[5], shape, jnp.float32)
        return (q, k, v, ksum, vsum), weights

    def value_and_grads(fn):
        def loss(weights, *a):
            out = fn(*a, window, chunk)
            return (out.astype(jnp.float32) * weights).sum(), out

        return jax.jit(jax.value_and_grad(
            loss, argnums=(1, 2, 3, 4, 5), has_aux=True
        ))

    operands, weights = inputs(args.seed, 1, 2 * window)
    (_, got), got_grads = value_and_grads(eva.kernel_eva_attention)(
        weights, *operands
    )
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = value_and_grads(eva.plain_eva_attention)(
            weights, *operands
        )
    wide = lambda z: z.astype(jnp.float32)
    off = lambda a, b: float(
        jnp.abs(wide(a) - wide(b)).max() / jnp.abs(wide(b)).max()
    )
    errors = dict(o=off(got, want), **{
        "d" + name: off(a, b)
        for name, a, b in zip(NAMES, got_grads, want_grads)
    })
    ok = all(e <= TOLERANCE for e in errors.values())
    report = dict(
        check="eva kernels against the plain twin", steps=2 * window,
        heads=heads, head_dim=d, window=window, chunk=chunk,
        tolerance=TOLERANCE, errors=errors, ok=ok,
        device=jax.devices()[0].device_kind,
    )
    print(json.dumps(report), flush=True)

    if args.time:
        operands, weights = inputs(args.seed + 1, 2, 8 * window)
        own = eva.sub_block
        for block in args.blocks or [own(window)]:
            eva.sub_block = lambda w, block=block: block
            eva._differentiable.cache_clear()
            forward = jax.jit(
                lambda *a: eva.kernel_eva_attention(*a, window, chunk)
            )
            both = value_and_grads(eva.kernel_eva_attention)
            timed = {}
            try:
                jax.block_until_ready(both(weights, *operands))
            except Exception as e:  # Mosaic refuses the shape: say so, go on
                print(json.dumps(dict(
                    sub_block=block, refused=str(e)[-300:]
                )), flush=True)
                continue
            for name, call in (
                ("forward_ms", lambda: forward(*operands)),
                ("forward_backward_ms", lambda: both(weights, *operands)),
            ):
                jax.block_until_ready(call())
                t0 = time.perf_counter()
                for _ in range(5):
                    out = call()
                jax.block_until_ready(out)
                timed[name] = 1e3 * (time.perf_counter() - t0) / 5
            print(json.dumps(dict(
                timing="one layer's calls, 2 x 32 x 16384 x 128",
                sub_block=block, **timed,
            )), flush=True)
        eva.sub_block = own
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
