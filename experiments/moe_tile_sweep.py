#!/usr/bin/env python3
"""The grouped kernels' tiles at a shape of your choosing, on the chip.

    chiprun -- python experiments/moe_tile_sweep.py --shape K N [--shape K N ...]
        [--rows M] [--groups G] [--peers P] [--held] [--tgmm]
        [--tiles tm,tk,tn ...] [--seeds N ...] [--reps R]

Needs a TPU (exits 4 without one; ``--rehearse-cpu`` runs the kernels through
the Pallas interpreter on the CPU, to find a wrong argument before a chip call:
its times mean nothing).

``--shape K N`` names the products that contract ``K`` into ``N``, which are
the ones that ask ``ops/moe._tiling(K, N)``: ``gmm`` ``[M, K] x [G, K, N]``
(forward) and ``gmm^T`` ``[M, K] x [G, N, K]^T`` (to the rows of a ``[G, N,
K]`` projection); with ``--tgmm`` also ``tgmm`` ``[M, K]^T [M, N] -> [G, K,
N]``, which asks ``_tiling(K, N, contracts_k=False)``.  Each is run as the
program runs it (``ops/moe._gmm`` / ``_gmm_transposed`` / ``_tgmm``, the peer
axis already folded: ``--rows`` sorted rows in ``--groups`` groups, a peer's
``G / P`` groups summing to its ``M / P`` rows, sizes drawn multinomially)
with ``_tiling`` answering the tile under test: first the one the rule picks,
then ``--tiles`` (default: :data:`CANDIDATES` that fit the shape).

``--held`` runs a share of the experts instead, as the A.X-K1 cell does:
``ops/moe.held_matmul``'s products under ``vmap`` over ``--peers`` (one
``megablox`` call a peer on the folded rows, ``group_offset`` and
``existing_out``), a peer's ``G / P`` held groups drawn as the first of 24
times as many (8 of 192 experts), so that most of its rows lie in no group.

One JSON line a candidate and seed: the product, the tile, whether the rule
picks it, ms a call (``--reps`` calls timed together after one warm call),
TFLOP/s over the rows that lie in a group, and the largest difference from
``lax.ragged_dot`` in float32 (the masked einsum for ``--held``) over the
largest value, held to :data:`TOLERANCE`.  A tile Mosaic refuses is reported
as refused.  PERF.md section 6 (PR 45) quotes these lines.
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
# bfloat16 results (2^-9 of a value) of float32 sums: 2-3e-3 is what comes.
TOLERANCE = 5e-3
CANDIDATES = [
    (256, 1024, 1024), (256, 2048, 1024), (256, 2048, 512), (512, 2048, 512),
    (256, 1024, 2048), (512, 1024, 1024),
]


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(call, reps):
    import jax

    jax.block_until_ready(call())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call()
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def products(k, n, args):
    """``(name, run(lhs, rhs, sizes), want(lhs, rhs, sizes), lhs shape, rhs
    shape)`` for each product of ``--shape k n``; operands carry the peer
    axis first under ``--held`` and have it folded otherwise."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dpwa_tpu.ops import moe

    p, m, g = args.peers, args.rows, args.groups
    wide = dict(preferred_element_type=jnp.float32)
    if args.held:
        over_peers = lambda plain, kernel, rows_out=True: jax.vmap(
            moe._peer_by_peer(plain, kernel, rows_out)
        )
        plain_t = lambda a, b, s: moe._plain_gmm(a, b, s, transpose_rhs=True)
        kernel_t = lambda *a: moe._kernel_gmm(*a, transpose_rhs=True)
        a, b = (p, m // p), (p, g // p)
        found = [
            ("gmm", over_peers(moe._plain_gmm, moe._kernel_gmm),
             jax.vmap(moe._plain_gmm), a + (k,), b + (k, n)),
            ("gmm^T", over_peers(plain_t, kernel_t), jax.vmap(plain_t),
             a + (k,), b + (n, k)),
        ]
        if args.tgmm:
            found.append((
                "tgmm", over_peers(moe._plain_tgmm, moe._kernel_tgmm, False),
                jax.vmap(moe._plain_tgmm), a + (k,), a + (n,),
            ))
        return found
    found = [
        ("gmm", moe._gmm,
         lambda a, b, s: lax.ragged_dot(a, b, s, **wide), (m, k), (g, k, n)),
        ("gmm^T", moe._gmm_transposed,
         lambda a, b, s: lax.ragged_dot(a, jnp.swapaxes(b, 1, 2), s, **wide),
         (m, k), (g, n, k)),
    ]
    if args.tgmm:
        found.append((
            "tgmm", moe._tgmm,
            lambda a, b, s: lax.ragged_dot_general(
                a, b, s, moe._RAGGED_CONTRACTING, **wide
            ), (m, k), (m, n),
        ))
    return found


def group_sizes(seed, args):
    """``[P, G / P]`` int32: a peer's rows over its groups, multinomially; a
    held share's groups are the first of 24 times as many."""
    import numpy as np

    rng = np.random.default_rng(seed)
    per_peer = args.groups // args.peers
    among = per_peer * (24 if args.held else 1)
    return np.stack([
        rng.multinomial(args.rows // args.peers, np.full(among, 1 / among))
        for _ in range(args.peers)
    ])[:, :per_peer].astype(np.int32)


def sweep(k, n, args) -> bool:
    import jax
    import jax.numpy as jnp

    from dpwa_tpu.ops import moe

    fits = lambda t: t[1] <= -(-k // 128) * 128 and t[2] <= -(-n // 128) * 128
    ok = True
    for name, run, want_of, lhs_shape, rhs_shape in products(k, n, args):
        picked = moe._tiling(k, n, contracts_k=name != "tgmm")
        want_of = jax.jit(want_of)
        for tiling in [picked] + [
            t for t in args.tiles or filter(fits, CANDIDATES) if t != picked
        ]:
            # (A lambda of its own: jit's cache is keyed by the function.)
            fn = jax.jit(lambda a, b, s: run(a, b, s))
            line = dict(
                product=name, shape=[k, n], rows=args.rows, groups=args.groups,
                held=args.held, tiling=tiling, picked=tiling == picked,
            )
            for seed in args.seeds:
                key = jax.random.key(seed)
                lhs = jax.random.normal(key, lhs_shape, jnp.bfloat16)
                rhs = jax.random.normal(
                    jax.random.fold_in(key, 1), rhs_shape, jnp.bfloat16
                )
                sizes = group_sizes(seed, args)
                used = int(sizes.sum())
                sizes = jnp.asarray(sizes if args.held else sizes.reshape(-1))
                want = want_of(lhs, rhs, sizes).astype(jnp.float32)
                try:
                    with mock.patch.object(moe, "_tiling", lambda *a, **kw: tiling):
                        got = fn(lhs, rhs, sizes).astype(jnp.float32)
                    ms = timed(lambda: fn(lhs, rhs, sizes), args.reps)
                except Exception as e:  # Mosaic refuses the tile: say so, go on
                    say(**line, seed=seed, refused=str(e)[-300:])
                    break
                error = float(jnp.abs(got - want).max() / jnp.abs(want).max())
                ok = ok and (tiling != picked or error <= TOLERANCE)
                say(**line, seed=seed, ms=round(ms, 4),
                    tflops=round(2e-9 * used * k * n / ms, 2), error=error)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=2, action="append",
                    required=True, metavar=("K", "N"))
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--groups", type=int, default=128)
    ap.add_argument("--peers", type=int, default=2)
    ap.add_argument("--held", action="store_true")
    ap.add_argument("--tgmm", action="store_true")
    ap.add_argument("--tiles", nargs="*", default=[],
                    type=lambda s: tuple(int(v) for v in s.split(",")))
    ap.add_argument("--seeds", type=int, nargs="+", default=[7, 11])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    if args.rehearse_cpu:
        from jax.experimental.pallas import tpu as pltpu

        with mock.patch.object(jax, "default_backend", lambda: "tpu"), (
            pltpu.force_tpu_interpret_mode()
        ):
            ok = all([sweep(k, n, args) for k, n in args.shape])
        say(ok=ok, device="cpu, kernels interpreted: no time here is a device's")
        return 0 if ok else 1
    if jax.devices()[0].platform != "tpu":
        print("moe_tile_sweep.py needs a TPU", file=sys.stderr)
        return 4
    from dpwa_tpu.utils.launch import enable_compile_cache

    enable_compile_cache()
    ok = all([sweep(k, n, args) for k, n in args.shape])
    say(ok=ok, device=jax.devices()[0].device_kind)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
