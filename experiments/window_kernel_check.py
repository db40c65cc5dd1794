#!/usr/bin/env python3
"""The windowed attention kernels against the masked softmax, and timed beside
the causal call, one layer's call at a shape of your choosing, on the chip.

    chiprun -- python experiments/window_kernel_check.py
        [--shape S H KV T D ...] [--window W] [--reps R] [--seed N]
        [--routing SEED ...]

Needs a TPU (exits 4 without one; ``--rehearse-cpu`` runs a toy shape through
the Pallas interpreter on the CPU, to find a wrong argument before a chip
call: its times mean nothing).

``--shape S H KV T D``: ``q [S, H, T, D]``, ``k [S, KV, T, D]`` heads first
and ``v [S, T, KV, D]`` positions first, bfloat16, as the kernels take them
since PR 54 (default: the Mellum cell's, 2 x 32 / 4 x 4,096 x 128, the peer
axis folded into ``S``; and T 8,192).  For
each shape, one JSON line a candidate with ``forward_ms`` and
``forward_backward_ms`` (``--reps`` calls timed together after a warm one, the
least of three such sets):

- ``window``: ``ops/eva.causal_attention(..., window=W)``, with the largest
  difference of its ``o dq dk dv`` from the plain twin's over the twin's
  largest value (both round to bfloat16: :data:`TOLERANCE`).  The twin is the
  masked-softmax einsum of ``ops/ulysses.single_device_attention`` (``impl=
  "dense"``, the same ``window``) in float32 on the same bfloat16 operands, a
  query head at a time so that its ``[T, T]`` scores fit;
- ``causal``: the same call without a window, the whole triangle: what a full
  layer runs, and what the window saves.

``pairs`` says what the band owes of the triangle, from shapes
(``benchmark/flops_window.pairs``).

``--routing SEED ...`` reads instead, a seed, how the Mellum cell's program
stands against its plain reference on the model check's own sample (seeded
weights of one peer, the first 2,048 positions of one sequence, the program
in the cell's compute type): the largest difference between the program's
router logits and the reference's, the smallest ``eps`` that accepts every
set (``margin``, against ``ROUTING_EPS``), and the logits' error as ``run.py``
reports it (against ``MODEL_TOLERANCE``).  Counts, not times.

PERF.md section 6 (PR 49) quotes these lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOLERANCE = 2e-2
CELLS = [
    (2, 32, 4, 4096, 128),  # mellum2-lora-stacked2-t4096
    (2, 32, 4, 8192, 128),  # the same at the length the guide pairs with experts
]


def say(**kw):
    print(json.dumps(kw), flush=True)


def routing_report(seeds, rehearsal: bool) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import reference, run, traffic
    from benchmark.references import window_moe_decoder as plain
    from dpwa_tpu.models.llama import routing_of

    cell = run.load_cell("mellum2-lora-stacked2-t4096", rehearsal)
    config, spec = cell.config, cell.traffic
    built = cell.builder.build(config, spec)
    model = cell.builder.model_of(config, spec["seq_len"])
    generate = traffic.make_generator(
        spec["task"], built.batch_shape, 1, spec["per_peer_batch"]
    )
    rms = lambda z: jnp.sqrt(jnp.mean(jnp.square(z)))

    @jax.jit
    def report(params, tokens):
        got, sown = model.apply(params, tokens, mutable=["intermediates"])
        routing = routing_of(sown)
        want, details = plain.forward_with_routing(
            config, params, tokens, routing["experts"]
        )
        return dict(
            router_logit_error=jnp.max(
                jnp.abs(routing["logits"] - details["logits"])
            ),
            margin=details["margin"].max(),
            model_vs_reference=rms(got.astype(jnp.float32) - want) / rms(want),
        )

    ok = True
    for seed in seeds:
        key = jax.random.key(seed)
        params = jax.jit(built.init_fn)(jax.random.fold_in(key, 0))
        batch = jax.tree.map(
            lambda v: v[0], generate(jax.random.fold_in(key, 1), 0)
        )
        said = {k: float(v) for k, v in report(
            params, built.reference_inputs(batch)
        ).items()}
        ok = ok and said["margin"] <= plain.ROUTING_EPS and (
            said["model_vs_reference"] <= reference.MODEL_TOLERANCE
        )
        say(seed=seed, positions=int(built.reference_inputs(batch).shape[1]),
            eps=plain.ROUTING_EPS, tolerance=reference.MODEL_TOLERANCE,
            platform=jax.devices()[0].platform, **said)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=5, action="append")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--routing", type=int, nargs="+", default=[])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse_cpu:
        print("window_kernel_check.py needs a TPU", file=sys.stderr)
        return 4
    from benchmark import flops_window
    from dpwa_tpu.ops import eva
    from dpwa_tpu.ops.ulysses import single_device_attention
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    if args.routing:
        return routing_report(args.routing, args.rehearse_cpu)
    shapes, window = args.shape or CELLS, args.window
    if args.rehearse_cpu:
        shapes, window, args.reps = [(1, 4, 2, 512, 128)], 128, 1

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
    turned = lambda z: jnp.swapaxes(z, -3, -2)
    oks = []
    for S, H, KV, T, D in shapes:
        keys = jax.random.split(jax.random.key(args.seed), 4)
        q = jax.random.normal(keys[0], (S, H, T, D), jnp.bfloat16)
        k = jax.random.normal(keys[1], (S, KV, T, D), jnp.bfloat16)
        v = jax.random.normal(keys[2], (S, T, KV, D), jnp.bfloat16)
        weights = jax.random.normal(keys[3], (S, T, H, D), jnp.float32)  # o's
        scale = D ** -0.5
        if not eva.causal_kernels_take(T, D, H, KV, q.dtype, window):
            say(shape=[S, H, KV, T, D], window=window, refused="not a shape the kernels take")
            oks.append(False)
            continue

        def twin_head(head):
            """``(o, dq, dk, dv)`` of one query head of one sequence on its
            head of keys, by the masked softmax in float32."""
            def plain(q, k, v):
                return single_device_attention(
                    turned(wide(q)), turned(wide(k)), wide(v), causal=True,
                    window=window, impl="dense", sm_scale=scale,
                )

            (_, o), grads = value_and_grads(plain)(*head)
            return (o, *grads)

        def twin():
            # A query head of a sequence at a time, each operand as it lies.
            heads = lambda z: jnp.repeat(z, H // z.shape[1], axis=1).reshape(
                S * H, 1, 1, T, D
            )
            o, dq, dk, dv = jax.lax.map(
                twin_head,
                (turned(heads(turned(weights))), heads(q), heads(k),
                 turned(heads(turned(v)))),
            )
            first = lambda z: z.reshape(S, H, T, D)
            summed = lambda z: z.reshape(S, KV, H // KV, T, D).sum(2)
            return (
                turned(first(turned(o))), first(dq), summed(first(dk)),
                turned(summed(first(turned(dv)))),
            )

        def measure(name, band, want=None):
            fn = lambda q, k, v: eva.causal_attention(
                q, k, v, scale, not on_chip, window=band
            )
            both = value_and_grads(fn)
            (_, o), grads = both(weights, q, k, v)
            forward = jax.jit(fn)
            line = dict(
                shape=[S, H, KV, T, D], candidate=name, window=band,
                pairs=flops_window.pairs(T, band),
                forward_ms=timed(lambda: forward(q, k, v)),
                forward_backward_ms=timed(lambda: both(weights, q, k, v)),
            )
            if want is not None:
                line["off_twin"] = dict(
                    zip(("o", "dq", "dk", "dv"), map(off, (o, *grads), want))
                )
                line["ok"] = max(line["off_twin"].values()) <= TOLERANCE
                oks.append(line["ok"])
            say(**line)

        measure("window", window, jax.jit(twin)())
        measure("causal", None)
    return 0 if all(oks) else 1


if __name__ == "__main__":
    sys.exit(main())
