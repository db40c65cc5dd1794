#!/usr/bin/env python3
"""Causal attention on the EVA core's kernels against the library's flash
kernels, one layer's call at a shape of your choosing, on the chip.

    chiprun -- python experiments/attention_kernel_sweep.py
        [--shape S H KV T D ...] [--variant WINDOW,BLOCK ...] [--ungrouped]
        [--reps R] [--seed N]

Needs a TPU (exits 4 without one; ``--rehearse-cpu`` runs toy shapes through
the Pallas interpreter on the CPU, to find a wrong argument before a chip
call: its times mean nothing).

``--shape S H KV T D``: ``q [S, H, T, D]``, ``k``, ``v`` ``[S, KV, T, D]``
bfloat16, heads first as the library's kernels take them; ours take ``v``
(and give ``o``, ``dv``) with positions first since PR 54, and are handed
``v`` and the loss's weights turned so, outside what is timed (default: the
four cells' shapes, the peer axis folded into ``S``).  For each shape, one JSON
line a candidate with ``forward_ms`` and ``forward_backward_ms`` (``--reps``
calls timed together after a warm one, the least of three such sets) and, for
ours, the largest difference from the library's ``o dq dk dv`` over the
largest value (both round to bfloat16: :data:`TOLERANCE`):

- ``library``: what ``ops/ulysses.single_device_attention`` calls where the
  rule does not take a shape: ``k``, ``v`` repeated to ``H`` heads, then
  ``flash_attention`` with ``_flash_block_sizes(T, D)``;
- ``ours``: ``ops/eva.causal_attention`` with the window and the block its
  rules pick (``causal_window``, ``sub_block``);
- ``--variant WINDOW,BLOCK``: the same with that window and block where they
  divide the shape (a window over 2,048 with the VMEM limit raised to 100 MB:
  the unrolled pairs hold more than ``_layout`` reckons);
- ``--ungrouped``: ours on ``k``, ``v`` repeated to ``H`` heads, the group's
  ``dk``, ``dv`` summed by XLA from per-query-head results.

PERF.md section 6 (PR 46) quotes these lines.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOLERANCE = 2e-2
CELLS = [
    (2, 32, 8, 4096, 128),   # mistral7b-lora-stacked2-t4096
    (16, 32, 8, 512, 128),   # mistral7b-lora-stacked2-t512
    (2, 16, 16, 4096, 128),  # olmoe-lora-stacked2-t4096
    (2, 20, 1, 4096, 128),   # jamba2-lora-period14-stacked2
]


def say(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=5, action="append")
    ap.add_argument("--variant", action="append", default=[])
    ap.add_argument("--ungrouped", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse_cpu:
        print("attention_kernel_sweep.py needs a TPU", file=sys.stderr)
        return 4
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    from dpwa_tpu.ops import eva
    from dpwa_tpu.ops.ulysses import _flash_block_sizes
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    shapes = args.shape or CELLS
    if args.rehearse_cpu:
        shapes = [(1, 4, 2, 256, 128)]
        args.reps = 1
        library.pl.pallas_call = functools.partial(
            library.pl.pallas_call, interpret=True
        )
    variants = [tuple(int(n) for n in v.split(",")) for v in args.variant]

    def value_and_grads(fn):
        def loss(weights, *a):
            out = fn(*a)
            return (out.astype(jnp.float32) * weights).sum(), out

        return jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3), has_aux=True))

    def timed(call):
        jax.block_until_ready(call())
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = call()
            jax.block_until_ready(out)
            best = min(best, 1e3 * (time.perf_counter() - t0) / args.reps)
        return best

    wide = lambda z: z.astype(jnp.float32)
    off = lambda a, b: float(jnp.abs(wide(a) - wide(b)).max() / jnp.abs(wide(b)).max())
    oks = []
    for S, H, KV, T, D in shapes:
        keys = jax.random.split(jax.random.key(args.seed), 4)
        q = jax.random.normal(keys[0], (S, H, T, D), jnp.bfloat16)
        k, v = (
            jax.random.normal(key, (S, KV, T, D), jnp.bfloat16)
            for key in keys[1:3]
        )
        weights = jax.random.normal(keys[3], q.shape, jnp.float32)
        scale = D ** -0.5
        full = lambda z: jnp.repeat(z, H // KV, axis=1)

        def by_library(q, k, v):
            return library.flash_attention(
                q, full(k), full(v), causal=True, sm_scale=scale,
                block_sizes=_flash_block_sizes(T, D),
            )

        turned = lambda z: z.swapaxes(1, 2)

        def ours(q, k, v):
            return eva.causal_attention(q, k, v, scale, not on_chip)

        def measure(name, fn, want=None, positions_first=False, **said):
            """``positions_first``: ``v`` and the weights (so ``o``, ``dv``)
            lie ``[S, T, h, D]``, as ours take and give them."""
            both = value_and_grads(fn)
            lying = turned if positions_first else (lambda z: z)
            operands = (lying(weights), q, k, lying(v))
            try:
                (_, o), (dq, dk, dv) = both(*operands)
                jax.block_until_ready(dv)
            except Exception as e:  # Mosaic refuses the shape: say so, go on
                say(shape=[S, H, KV, T, D], candidate=name, refused=str(e)[-300:], **said)
                return None
            got = (lying(o), dq, dk, lying(dv))
            forward = jax.jit(lambda *a: fn(*a))  # a candidate, a program
            line = dict(
                shape=[S, H, KV, T, D], candidate=name, **said,
                forward_ms=timed(lambda: forward(*operands[1:])),
                forward_backward_ms=timed(lambda: both(*operands)),
            )
            if want is not None:
                line["off_library"] = dict(
                    zip(("o", "dq", "dk", "dv"), map(off, got, want))
                )
                line["ok"] = max(line["off_library"].values()) <= TOLERANCE
                oks.append(line["ok"])
            say(**line)
            return got

        want = measure("library", by_library)
        rule = (eva.causal_window(T), eva.sub_block(eva.causal_window(T)))
        measure(
            "ours", ours, want, True, window=rule[0], block=rule[1], rule=True
        )
        if args.ungrouped and H != KV:
            every = lambda z: jnp.repeat(z, H // KV, axis=2)
            measure(
                "ours_ungrouped", lambda q, k, v: ours(q, full(k), every(v)),
                want, True, window=rule[0], block=rule[1],
            )
        for window, block in variants:
            if (window, block) == rule or T % window or window % block:
                continue
            limit = eva.vmem_limit if window <= 2048 else (lambda need: 100 * 2 ** 20)
            with mock.patch.object(eva, "causal_window", lambda T: window), \
                    mock.patch.object(eva, "sub_block", lambda w: block), \
                    mock.patch.object(eva, "vmem_limit", limit):
                eva._differentiable.cache_clear()
                measure(
                    "ours", ours, want, True, window=window, block=block,
                    rule=False,
                )
            eva._differentiable.cache_clear()
    return 0 if all(oks) else 1


if __name__ == "__main__":
    sys.exit(main())
