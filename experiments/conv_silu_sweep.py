#!/usr/bin/env python3
"""``ops/ssm.conv_silu``'s two kernels against the plain form, and their chunk
and channel block timed inside one Mamba block's whole gradient, on the chip.

    chiprun -- python experiments/conv_silu_sweep.py
        [--variant CHUNK,BLOCK ...] [--reps R] [--seed N]

Needs a TPU (exits 4 without one; ``--rehearse-cpu`` runs toy shapes through
the Pallas interpreter on the CPU, to find a wrong argument before a chip
call: its times mean nothing).

One JSON line a candidate, at the Jamba cell's shapes (two peers under
``vmap``, 1 x 4,096 tokens each, the published widths, bfloat16 stream):

- ``check``: ``conv_silu`` and its gradient to ``x`` by the kernels against
  autodiff of ``silu(causal_conv1d(...))`` on the same operands, the largest
  difference over the plain form's largest value (both round to bfloat16:
  :data:`TOLERANCE`), and the share of values that differ at all;
- ``block``: one ``models/llama.Block`` of the cell's configuration (a Mamba
  mixer and its dense MLP) under ``jax.checkpoint`` with the cell's policy,
  the gradient to the adapters and to the block's input, ``--reps`` calls
  timed together after a warm one, the least of three such sets, ms a call:
  ``plain`` with the convolution as the parent has it, then the kernels at
  each ``--variant`` (``ops/ssm.conv_chunk`` and ``conv_block`` replaced for
  the trace; ``rule`` is what they give by themselves).  The kernels alone
  say little: what XLA does around the two calls is most of the difference
  (ROADMAP lesson h).

PERF.md section 6 (PR 56) quotes these lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOLERANCE = 2e-2
CELL = "jamba2-lora-period14-stacked2"
VARIANTS = [
    (512, 1024), (256, 1024), (1024, 1024), (512, 512), (1024, 512),
    (2048, 512), (128, 1024), (512, 2560), (4096, 256),
]


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(fn, args, reps: int) -> float:
    """ms a call: ``reps`` calls together, the least of three sets."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return 1e3 * best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", nargs="*", default=None)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from benchmark import run
    from dpwa_tpu.models import llama
    from dpwa_tpu.ops import ssm
    from dpwa_tpu.utils.launch import enable_compile_cache

    rehearsal = args.rehearse_cpu
    if jax.default_backend() != "tpu" and not rehearsal:
        print("no TPU: pass --rehearse-cpu for a toy run", file=sys.stderr)
        return 4
    enable_compile_cache()
    say(device=jax.devices()[0].device_kind, rehearsal=rehearsal)
    variants = [
        tuple(int(v) for v in item.split(",")) for item in args.variant or ()
    ] or (VARIANTS if not rehearsal else [(16, 128), (32, 128)])

    cell = run.load_cell(CELL, rehearsal)
    config, spec = cell.config, cell.traffic
    cfg = cell.builder.model_of(config, spec["seq_len"]).cfg
    peers, steps = spec["peers"], spec["seq_len"] if not rehearsal else 64
    channels = cfg.mamba_expand * cfg.d_model
    keys = jax.random.split(jax.random.key(args.seed), 6)
    kernels = ssm.kernel_conv_silu if not rehearsal else (
        ssm.interpreted_conv_silu
    )

    # 1. The kernels against the plain form, the cell's shape.
    x = jax.random.normal(
        keys[0], (peers, 1, steps, channels)
    ).astype(cfg.dtype)
    g = jax.random.normal(keys[1], x.shape).astype(cfg.dtype)
    bound = cfg.mamba_d_conv ** -0.5
    w = jax.random.uniform(
        keys[2], (peers, cfg.mamba_d_conv, channels), minval=-bound,
        maxval=bound,
    ).astype(cfg.param_dtype)
    b = jax.random.uniform(
        keys[3], (peers, channels), minval=-bound, maxval=bound
    ).astype(cfg.param_dtype)

    def both(fn):
        def run_(x, g, w, b):
            y, pull = jax.vjp(lambda x: jax.vmap(fn)(x, w, b), x)
            return y, pull(g)[0]
        return jax.jit(run_)

    got, want = both(kernels)(x, g, w, b), both(ssm.plain_conv_silu)(x, g, w, b)
    wide = lambda v: v.astype(jnp.float32)
    for name, a, c in zip(("y", "dx"), got, want):
        a, c = wide(a), wide(c)
        say(
            check=name, off=float(jnp.abs(a - c).max() / jnp.abs(c).max()),
            differ=float((a != c).mean()), tolerance=TOLERANCE,
            ok=bool(jnp.abs(a - c).max() <= TOLERANCE * jnp.abs(c).max()),
        )

    # 2. One Mamba block's gradient under the cell's checkpoint.
    block = llama.nn.remat(
        llama.Block, prevent_cse=(False, False, True, True),
        policy=llama._checkpoint_policy(cfg, 0),
    )(cfg, 0)
    h = jax.random.normal(
        keys[4], (peers, 1, steps, cfg.d_model)
    ).astype(cfg.stream_dtype)
    positions = jnp.arange(steps)
    params = jax.jit(jax.vmap(
        lambda key: block.init(key, h[0], positions)
    ))(jax.random.split(keys[5], peers))
    flat = traverse_util.flatten_dict(params)
    adapters = {k: v for k, v in flat.items() if "lora_" in k[-1]}
    base = {k: v for k, v in flat.items() if "lora_" not in k[-1]}

    def gradient():
        def loss(adapters, h):
            tree = traverse_util.unflatten_dict({**base, **adapters})
            out = jax.vmap(lambda p, v: block.apply(p, v, positions))(tree, h)
            return jnp.sum(wide(out) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    def block_ms(conv):
        jax.clear_caches()  # the kernels' calls are jitted: trace them anew
        with mock.patch.object(ssm, "conv_silu", conv):
            return timed(gradient(), (adapters, h), args.reps)

    say(block="plain", ms=block_ms(ssm.plain_conv_silu))
    say(
        block="rule", chunk=ssm.conv_chunk(steps),
        channels=ssm.conv_block(channels), ms=block_ms(kernels),
    )
    for chunk, width in variants:
        if steps % chunk or channels % width:
            continue
        with mock.patch.object(ssm, "conv_chunk", lambda steps: chunk), \
                mock.patch.object(ssm, "conv_block", lambda channels: width):
            try:
                say(block="kernels", chunk=chunk, channels=width,
                    ms=block_ms(kernels))
            except Exception as e:  # a block Mosaic refuses: say so, go on
                say(block="kernels", chunk=chunk, channels=width,
                    error=str(e)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main())
