"""Shared example-launcher plumbing: transport + device policy selection.

Every example (the reference keeps one per benchmark config,
``examples/mnist`` etc. — SURVEY.md §2) exposes the same two knobs:

- ``--transport ici|stacked`` — ``ici`` runs one SPMD process over a device
  mesh (one device per peer, the real multi-chip layout); ``stacked`` runs
  every peer on ONE device as a stacked leading axis (the single-chip
  benchmarking mode, SURVEY.md §7 note: the dev box has one chip).
- ``--devices auto|native|cpu`` — device policy
  (:mod:`dpwa_tpu.utils.devices`): ``auto`` and ``native`` both run on the
  platform JAX selected and raise when it has fewer devices than the
  transport needs (one per peer for ``ici``, one for ``stacked``); only
  ``cpu`` gives the emulated host mesh.

:func:`build_transport` returns the transport plus the matching
state-init / train-step constructors, so an example's training loop is
identical across transports.
"""

from __future__ import annotations

import argparse
import os
from typing import NamedTuple, Optional, Tuple


def child_process_env(
    repo_root: Optional[str] = None,
    *,
    strip: Tuple[str, ...] = (
        "XLA_FLAGS",
        "JAX_PLATFORMS",
        "JAX_NUM_PROCESSES",
    ),
    platform: Optional[str] = "cpu",
) -> dict:
    """Environment for a spawned JAX worker process.

    Launchers that fork multi-process legs (the TCP free-run experiment,
    the multi-process DCN test) must not leak the parent's frozen platform
    choices: ``XLA_FLAGS``'s forced device count and ``JAX_PLATFORMS`` are
    parsed once at the child's first backend init, so inherited values
    silently misconfigure it.  Strips those, pins ``platform`` (None keeps
    the child's default resolution), and prepends ``repo_root`` to
    ``PYTHONPATH`` so in-repo imports work from any cwd."""
    env = {k: v for k, v in os.environ.items() if k not in strip}
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    if repo_root is not None:
        env["PYTHONPATH"] = os.pathsep.join(
            filter(None, (repo_root, env.get("PYTHONPATH")))
        )
    return env


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    directory is set in code.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``, derived from this package's location: the
    path is part of the cache key, so it must not move between runs."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def add_transport_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument(
        "--transport", choices=("ici", "stacked"), default="ici",
        help="'ici': SPMD over a device mesh (one device per peer); "
        "'stacked': all peers on ONE device as a stacked axis — the "
        "single-chip benchmarking mode",
    )
    ap.add_argument(
        "--devices", default="auto", choices=("auto", "cpu", "native"),
        help="'auto'/'native': the platform jax selected, error when it is "
        "short of devices; 'cpu': the emulated host mesh",
    )
    ap.add_argument(
        "--wire-dtype", default=None, choices=("f32", "bf16", "int8"),
        help="override protocol.wire_dtype: compress the SHIPPED replica "
        "(bf16: half the exchange bytes; int8: ~3.9x fewer, unbiased "
        "stochastic rounding — ops/quantize.py); default keeps the "
        "config file's setting",
    )


def apply_wire_dtype(cfg, wire_dtype: Optional[str]):
    """Return ``cfg`` with ``protocol.wire_dtype`` overridden (None =
    unchanged).  Configs are frozen dataclasses; ``dataclasses.replace``
    re-runs validation."""
    if wire_dtype is None:
        return cfg
    import dataclasses

    return dataclasses.replace(
        cfg, protocol=dataclasses.replace(cfg.protocol, wire_dtype=wire_dtype)
    )


class TransportBundle(NamedTuple):
    transport: object
    init_state: object  # (stacked_params, opt, transport, ...) -> state
    make_step: object  # (loss_fn, opt, transport, ...) -> step_fn
    eval_transport: Optional[object]  # None => single-device eval
    batch_sharding: Optional[object]  # peer sharding for staged batches
    config: object = None  # the EFFECTIVE config (wire_dtype applied)


def build_transport(
    cfg,
    transport: str = "ici",
    devices: str = "auto",
    wire_dtype: Optional[str] = None,
):
    """Select + construct the transport; returns a :class:`TransportBundle`.

    Call before creating any arrays: the device policy may decide the JAX
    platform, which is frozen at first backend use.

    ``wire_dtype`` (the ``--wire-dtype`` flag from
    :func:`add_transport_args`) is applied HERE so a caller can never
    accept the flag yet silently ignore it; read the effective config
    back from ``bundle.config``."""
    from dpwa_tpu.utils.devices import ensure_devices

    cfg = apply_wire_dtype(cfg, wire_dtype)
    ensure_devices(1 if transport == "stacked" else cfg.n_peers, mode=devices)
    enable_compile_cache()
    if transport == "stacked":
        from dpwa_tpu.parallel.stacked import (
            StackedTransport,
            init_stacked_state,
            make_stacked_train_step,
        )

        return TransportBundle(
            transport=StackedTransport(cfg),
            init_state=init_stacked_state,
            make_step=make_stacked_train_step,
            eval_transport=None,
            batch_sharding=None,
            config=cfg,
        )
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
    from dpwa_tpu.train import init_gossip_state, make_gossip_train_step

    t = IciTransport(cfg, mesh=make_mesh(cfg))
    # Stage batches peer-sharded for the mesh path: a whole batch committed
    # to one device is resharded inside the jitted shard_map every step —
    # a copy through the first chip on a real mesh, and more than the
    # thread-starved forced-CPU mesh can always service.
    return TransportBundle(
        transport=t,
        init_state=init_gossip_state,
        make_step=make_gossip_train_step,
        eval_transport=t,
        batch_sharding=peer_sharding(t.mesh),
        config=cfg,
    )
