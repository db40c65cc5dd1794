#!/usr/bin/env python3
"""How the program's routing stands against the reference's, for a
``moe_decoder`` cell at the sizes it runs (on the chip) or its toys:

    python benchmark/moe_routing_report.py --workload <name> --seed <n> ...

For each seed: seeded weights of one peer, the builder's reference sample
(the first 256 positions of one sequence); the program in the cell's compute
dtype against ``benchmark/references/moe_decoder.py`` in float32 with the
program's routing verified.  Prints, a seed, the largest difference between
the program's router logits and the reference's (``ROUTING_EPS`` is three
times the largest seen on the v5e), the smallest ``eps`` that accepts every
set (``margin``), the share of (layer, token) pairs whose set differs from
the reference's own top-k, and the logits' error as ``run.py`` reports it.
A count, not a time: nothing here is a device metric."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", default=[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp

    from benchmark import run, traffic
    from benchmark.builders import moe_decoder
    from benchmark.references import moe_decoder as plain
    from dpwa_tpu.models.llama import routing_of

    cell = run.load_cell(args.workload, args.rehearse_cpu)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("moe_routing_report.py needs a TPU", file=sys.stderr)
        return run.EXIT_NO_ACCELERATOR
    config, spec = cell.config, cell.traffic
    built = moe_decoder.build(config, spec)
    model = moe_decoder.model_of(config, spec["seq_len"])
    top = config["num_experts_per_tok"]
    generate = traffic.make_generator(
        spec["task"], built.batch_shape, 1, spec["per_peer_batch"]
    )

    @jax.jit
    def report(params, tokens):
        got, sown = model.apply(params, tokens, mutable=["intermediates"])
        routing = routing_of(sown)
        want, details = plain.forward_with_routing(
            config, params, tokens, routing["experts"]
        )
        error = jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32) - want)))
        return dict(
            router_logit_error=jnp.max(
                jnp.abs(routing["logits"] - details["logits"])
            ),
            margin=details["margin"].max(),
            sets_differing=plain.routing_disagreement(
                details["logits"], routing["experts"], top
            ),
            model_vs_reference=error / jnp.sqrt(jnp.mean(jnp.square(want))),
        )

    for seed in args.seed:
        key = jax.random.key(seed)
        params = jax.jit(built.init_fn)(jax.random.fold_in(key, 0))
        batch = jax.tree.map(
            lambda v: v[0], generate(jax.random.fold_in(key, 1), 0)
        )
        said = {k: float(v) for k, v in report(
            params, built.reference_inputs(batch)
        ).items()}
        print(json.dumps(dict(
            workload=args.workload, seed=seed, eps=plain.ROUTING_EPS,
            platform=jax.devices()[0].platform, **said,
        )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
