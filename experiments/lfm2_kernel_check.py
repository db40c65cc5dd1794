#!/usr/bin/env python3
"""What the LFM2 cell asks of kernels the repo already had, on the chip.

    chiprun -- python experiments/lfm2_kernel_check.py [--attention] [--tiles]
        [--routing] [--seed N]

Needs a TPU (exits 4 without one).  Three questions, each a flag:

``--attention``: the library's flash kernels at a head size of 64.  At 2 x 32
heads x T 1,024 the output and the three gradients of (a) the kernels handed
64 as it is and (b) q, k, v zero-padded to 128 and the output sliced back
(``ops/ulysses.single_device_attention``'s rule) against the masked-softmax
einsum under ``jax.default_matmul_precision("highest")``, as the largest
difference over the largest value; then forward + backward of both at the
cell's 2 x 32 x 4,096 in ms.

``--tiles``: ``ops/moe._tiling`` at an expert width of 1,792.  At the cell's
32,768 sorted rows in 64 folded groups (two peers' 32 experts, a peer's groups
summing to its 16,384 rows, sizes drawn multinomially) every grouped product
of one expert layer's step (``gmm`` forward and transposed for the three
kernels, the adapters' narrow ``gmm`` and ``tgmm``, and the wide ``tgmm`` that
only full-tree training computes) is timed under the tiles ``_tiling`` picks
and under candidates (1,024 x 1,024 among them), and each is held to
``lax.ragged_dot`` (XLA's own grouped product) to 1e-2 of its largest value.
A tiling Mosaic refuses is reported as refused.

``--routing``: the two readings of ``references/conv_moe_decoder.CHOICE_EPS``:
the cell's model at the published widths from ``--seed``, its routing of one
sequence's first 256 tokens verified by the reference as the harness's model
check does (``logit_error``, ``set_margin``, ``bias_moved`` a layer), and the
same with the verified scores rounded to bfloat16, which has to be refused.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "lfm2-lora-stacked2-t4096"


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(call, reps=5):
    import jax

    jax.block_until_ready(call())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call()
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def attention(seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    from dpwa_tpu.ops.ulysses import _flash_block_sizes, single_device_attention

    heads, d = 32, 64

    def inputs(steps):
        keys = jax.random.split(jax.random.key(seed), 4)
        shape = (2, steps, heads, d)
        q, k, v = (jax.random.normal(key, shape, jnp.bfloat16) for key in keys[:3])
        return (q, k, v), jax.random.normal(keys[3], shape, jnp.float32)

    def native(q, k, v):
        heads_first = lambda x: x.transpose(0, 2, 1, 3)
        return flash_attention(
            heads_first(q), heads_first(k), heads_first(v), causal=True,
            sm_scale=d ** -0.5,
            block_sizes=_flash_block_sizes(q.shape[1], 128),
        ).transpose(0, 2, 1, 3)

    padded = lambda q, k, v: single_device_attention(q, k, v, causal=True)
    einsum = lambda q, k, v: single_device_attention(
        q, k, v, causal=True, impl="dense"
    )

    def value_and_grads(fn):
        def loss(weights, *a):
            out = fn(*a)
            return (out.astype(jnp.float32) * weights).sum(), out

        return jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3), has_aux=True))

    operands, weights = inputs(1024)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = value_and_grads(einsum)(weights, *operands)
    wide = lambda z: z.astype(jnp.float32)
    off = lambda a, b: float(jnp.abs(wide(a) - wide(b)).max() / jnp.abs(wide(b)).max())
    ok = True
    for name, fn in (("native64", native), ("padded128", padded)):
        try:
            (_, got), grads = value_and_grads(fn)(weights, *operands)
        except Exception as e:  # Mosaic refuses the shape: say so, go on
            say(attention=name, refused=str(e)[-400:])
            continue
        errors = dict(o=off(got, want), **{
            "d" + n: off(a, b) for n, a, b in zip("qkv", grads, want_grads)
        })
        ok = ok and (name != "padded128" or max(errors.values()) <= 1e-2)
        say(attention=name, steps=1024, errors=errors)
    operands, weights = inputs(4096)
    for name, fn in (("native64", native), ("padded128", padded)):
        both = value_and_grads(fn)
        try:
            say(attention=name, steps=4096, forward_backward_ms=timed(
                lambda: both(weights, *operands)
            ), forward_ms=timed(lambda: jax.jit(fn)(*operands)))
        except Exception as e:
            say(attention=name, steps=4096, refused=str(e)[-400:])
    return ok


def tiles(seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dpwa_tpu.ops import moe

    kernels = moe._kernels()
    rows, groups, d, f, r = 32768, 64, 2048, 1792, 16
    rng = np.random.default_rng(seed)
    sizes = np.concatenate([
        rng.multinomial(rows // 2, np.full(32, 1 / 32)) for _ in range(2)
    ]).astype(np.int32)
    group_sizes = jnp.asarray(sizes)
    key = jax.random.key(seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)

    # name -> (kind, lhs, rhs, the function's pick, candidates)
    wide_k2048 = [(256, 1024, 1024), (256, 1024, 896), (256, 2048, 896),
                  (256, 1024, 1792),
                  (256, 1024, 512), (256, 1024, 256), (512, 1024, 896),
                  (256, 2048, 1024), (512, 1024, 1024)]
    wide_k1792 = [(256, 1024, 1024), (256, 896, 1024), (256, 1792, 1024),
                  (256, 512, 1024),
                  (256, 256, 1024), (512, 896, 1024), (256, 896, 2048),
                  (256, 1792, 512), (512, 1024, 1024)]
    narrow_n1792 = [(512, 128, 896), (512, 128, 1792), (512, 128, 512),
                    (512, 128, 256)]
    cases = {
        "gmm gate/up [M,2048]x[G,2048,1792]": (
            "gmm", normal(0, (rows, d)), normal(1, (groups, d, f)),
            moe._tiling(d, f), wide_k2048),
        "gmm down [M,1792]x[G,1792,2048]": (
            "gmm", normal(2, (rows, f)), normal(3, (groups, f, d)),
            moe._tiling(f, d), wide_k1792),
        "gmm^T gate/up [M,1792]x[G,2048,1792]^T": (
            "gmmT", normal(4, (rows, f)), normal(1, (groups, d, f)),
            moe._tiling(f, d), wide_k1792),
        "gmm^T down [M,2048]x[G,1792,2048]^T": (
            "gmmT", normal(5, (rows, d)), normal(3, (groups, f, d)),
            moe._tiling(d, f), wide_k2048),
        "gmm adapter B [M,16]x[G,16,1792]": (
            "gmm", normal(6, (rows, r)), normal(7, (groups, r, f)),
            moe._tiling(r, f), narrow_n1792),
        "gmm adapter A [M,1792]x[G,1792,16]": (
            "gmm", normal(2, (rows, f)), normal(8, (groups, f, r)),
            moe._tiling(f, r), [(512, 896, 128), (512, 1024, 128)]),
        "tgmm adapter A [M,1792]^T[M,16]": (
            "tgmm", normal(2, (rows, f)), normal(9, (rows, r)),
            moe._tiling(f, r, False), [(512, 896, 128), (512, 1024, 128)]),
        "tgmm adapter B [M,16]^T[M,1792]": (
            "tgmm", normal(6, (rows, r)), normal(4, (rows, f)),
            moe._tiling(r, f, False), narrow_n1792),
        # Full-tree training's: no benchmark cell computes them under LoRA.
        "tgmm gate/up [M,2048]^T[M,1792]": (
            "tgmm", normal(0, (rows, d)), normal(4, (rows, f)),
            moe._tiling(d, f, False),
            [(256, 1024, 1024), (256, 1024, 896), (256, 2048, 896),
             (512, 1024, 896)]),
        "tgmm down [M,1792]^T[M,2048]": (
            "tgmm", normal(2, (rows, f)), normal(5, (rows, d)),
            moe._tiling(f, d, False),
            [(256, 1024, 1024), (256, 896, 1024), (256, 1792, 1024),
             (512, 896, 1024)]),
    }

    def call(kind, lhs, rhs, tiling):
        if kind == "tgmm":
            return kernels.tgmm(
                lhs.swapaxes(0, 1), rhs, group_sizes, lhs.dtype, tiling
            )
        return kernels.gmm(
            lhs, rhs, group_sizes, lhs.dtype, tiling,
            transpose_rhs=kind == "gmmT",
        )

    def plain(kind, lhs, rhs):
        if kind == "tgmm":
            return lax.ragged_dot_general(
                lhs, rhs, group_sizes, moe._RAGGED_CONTRACTING,
                preferred_element_type=jnp.float32,
            )
        if kind == "gmmT":
            rhs = jnp.swapaxes(rhs, 1, 2)
        return lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32
        )

    ok = True
    for name, (kind, lhs, rhs, picked, candidates) in cases.items():
        want = jax.jit(lambda a, b, kind=kind: plain(kind, a, b))(lhs, rhs)
        scale = float(jnp.abs(want).max())
        for tiling in [picked] + [c for c in candidates if c != picked]:
            fn = jax.jit(lambda a, b, kind=kind, t=tiling: call(kind, a, b, t))
            try:
                got = fn(lhs, rhs)
                error = float(jnp.abs(got.astype(jnp.float32) - want).max()) / scale
                ms = timed(lambda: fn(lhs, rhs))
            except Exception as e:
                say(product=name, tiling=tiling, refused=str(e)[-200:])
                continue
            ok = ok and (tiling != picked or error <= 1e-2)
            say(product=name, tiling=tiling, picked=tiling == picked,
                ms=round(ms, 4), error=error)
    return ok


def routing(seed: int) -> bool:
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark import run
    from benchmark.references import conv_moe_decoder as plain
    from dpwa_tpu.models.llama import routing_of

    cell = run.load_cell(CELL, rehearsal=False)
    builder = importlib.import_module("benchmark.builders.conv_moe_decoder")
    model = builder.model_of(cell.config, 256)
    key = jax.random.key(seed)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1), (1, 256), 0, cell.config["vocab_size"]
    )
    logits, sown = jax.jit(
        lambda p, t: model.apply(p, t, mutable=["intermediates"])
    )(params, tokens)
    sown = routing_of(sown)
    said = {}
    for name, how in (
        ("program", {}),
        # (A cast there and back is one XLA:TPU takes out again.)
        ("bf16_scores", dict(
            round_scores=lambda s: jax.lax.reduce_precision(s, 8, 7)
        )),
    ):
        want, details = jax.jit(
            lambda p, t, s, how=how: plain.forward_with_routing(
                cell.config, p, t, s, **how
            )
        )(params, tokens, sown)
        rms = lambda z: float(jnp.sqrt(jnp.mean(jnp.square(z))))
        said[name] = dict(
            logit_error=details["logit_error"].tolist(),
            set_margin=details["set_margin"].tolist(),
            bias_moved=details["bias_moved"].tolist(),
            refused_tokens=int(jnp.isnan(want).any(-1).sum()),
            model_vs_reference=rms(jnp.nan_to_num(logits - want)) / rms(
                jnp.nan_to_num(want)
            ),
        )
    say(routing=said, seed=seed, program_bias_moved=sown["bias_moved"].tolist(),
        choice_eps=plain.CHOICE_EPS, logit_eps=plain.LOGIT_EPS)
    return (
        said["program"]["refused_tokens"] == 0
        and said["bf16_scores"]["refused_tokens"] > 0
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attention", action="store_true")
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--routing", action="store_true")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("lfm2_kernel_check.py needs a TPU", file=sys.stderr)
        return 4
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    ok = True
    for flag, check in (
        (args.attention, attention), (args.tiles, tiles),
        (args.routing, routing),
    ):
        if flag:
            ok = check(args.seed) and ok
    say(ok=ok, device=jax.devices()[0].device_kind)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
