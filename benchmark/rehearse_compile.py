#!/usr/bin/env python3
"""Compile a cell's step for a v5e that is described, not attached, and print
what ``memory_analysis()`` says: the rehearsal that decides per-peer batch and
depth before any chip time is spent (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py <workload> \
        [--set key=value ...]     # override cell or config keys, e.g.
                                  # per_peer_batch=32 num_hidden_layers=4

Nothing runs, so this gives no time and no result; a compile that passes is
not a chip run.  It also compiles the reference's local update at the same
shapes, because the check has to fit beside the live state.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--no-reference", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference, run
    from dpwa_tpu.config import make_local_config

    loaded = run.load_cell(args.workload, rehearsal=False)
    config, cell, builder = loaded.config, loaded.traffic, loaded.builder
    for item in args.set:
        key, value = item.split("=", 1)
        target = cell if key in cell else config
        target[key] = json.loads(value)
    n, b = cell["peers"], cell["per_peer_batch"]

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = make_local_config(
        n, schedule=cell["schedule"], seed=cell["schedule_seed"],
        wire_dtype=cell["wire_dtype"], factor=cell["factor"],
        **({"pool_size": cell["pool_size"]} if cell.get("pool_size") else {}),
    )
    # The kernel dispatchers ask jax.default_backend(); answer as the chip.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        built = builder.build(config, cell)
        if cell["transport"] == "stacked":
            from dpwa_tpu.parallel.stacked import (
                StackedTrainState as State, StackedTransport,
                make_stacked_train_step as make_step,
            )

            transport = StackedTransport(cfg)
            peer = replicated = SingleDeviceSharding(topo.devices[0])
        else:
            from dpwa_tpu.parallel.ici import IciTransport
            from dpwa_tpu.train import (
                GossipTrainState as State, make_gossip_train_step as make_step,
            )

            mesh = Mesh(np.array(topo.devices[:n]), ("peers",))
            transport = IciTransport(cfg, mesh=mesh)
            peer = NamedSharding(mesh, P("peers"))
            replicated = NamedSharding(mesh, P())
        place = lambda tree, sh: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree
        )
        params = jax.eval_shape(
            jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), n)
        )
        optimizer = built.make_optimizer(
            jax.eval_shape(built.init_fn, jax.random.key(0))
        )
        opt_state = jax.eval_shape(jax.vmap(optimizer.init), params)
        vec = jax.ShapeDtypeStruct((n,), jnp.float32)
        state = State(
            params=place(params, peer), opt_state=place(opt_state, peer),
            clock=place(vec, peer),
            step=place(jax.ShapeDtypeStruct((), jnp.int32), replicated),
            model_state=None, loss=place(vec, peer),
        )
        from benchmark import traffic

        generate = traffic.make_generator(cell["task"], built.batch_shape, n, b)
        batch = place(
            jax.eval_shape(generate, jax.random.key(0), 0), peer
        )
        step_fn = make_step(
            built.loss_fn, optimizer, transport,
            exchange_filter=built.exchange_filter, overlap=cell["overlap"],
        )
        programs = {"step": lambda: jax.jit(
            step_fn, donate_argnums=(0,)
        ).lower(state, batch)}
        if not args.no_reference:
            local = reference.make_local_update(
                built.loss_fn, optimizer, built.exchange_filter
            )
            programs["reference_local_update"] = lambda: local.lower(
                state.params, state.opt_state, batch
            )
        report = dict(workload=args.workload, overrides=args.set, peers=n,
                      per_peer_batch=b)
        for name, lower in programs.items():
            t0 = time.perf_counter()
            compiled = lower().compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            report[name] = dict(
                compile_s=round(time.perf_counter() - t0, 1),
                argument_gb=ma.argument_size_in_bytes / 1e9,
                output_gb=ma.output_size_in_bytes / 1e9,
                temp_gb=ma.temp_size_in_bytes / 1e9,
                alias_gb=ma.alias_size_in_bytes / 1e9,
                total_gb=total / 1e9,
                collective_permute="collective-permute" in text,
                tpu_custom_call="tpu_custom_call" in text,
            )
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
