"""Device-mesh construction from the reference-style YAML config.

The contract (BASELINE.json:5, SURVEY.md §2 "Distributed communication
backend"): the YAML ``nodes:`` list that names TCP peers in the reference is
reinterpreted as a **device-mesh axis of the same length**.  One config file
drives either transport; the ICI transport ignores per-node host/port.

Multi-host: initialize ``jax.distributed`` before calling :func:`make_mesh`
and the global device list spans hosts; ``mesh_utils.create_device_mesh``
orders devices so that contiguous index ranges are intra-host — which is what
makes the hierarchical schedule's intra-group slots ride ICI and only the
inter-group slots cross DCN (SURVEY.md §5 "Distributed communication
backend").
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dpwa_tpu.config import DpwaConfig

PEER_AXIS = "peers"


def make_mesh(
    config: DpwaConfig,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = PEER_AXIS,
) -> Mesh:
    """A 1-D mesh whose axis length equals ``len(config.nodes)``."""
    n = config.n_peers
    if devices is None:
        devices = jax.devices()
        if len(devices) < n:
            raise RuntimeError(
                f"config names {n} peers but only {len(devices)} JAX "
                f"devices are visible; set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n} for "
                f"CPU emulation or use the TCP transport"
            )
        if len(devices) == n:
            # The whole slice: mesh_utils orders the ring along the torus.
            devices = mesh_utils.create_device_mesh((n,), devices=devices)
        else:
            # A prefix need not be a sub-torus (mesh_utils refuses 3 chips
            # of a 2x2): keep enumeration order.
            devices = devices[:n]
    return Mesh(np.asarray(devices).reshape(n), (axis_name,))


def peer_sharding(mesh: Mesh, axis_name: str = PEER_AXIS) -> NamedSharding:
    """Sharding that splits a leading peer-stacked axis across the mesh."""
    return NamedSharding(mesh, P(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
