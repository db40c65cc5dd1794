"""Device merge engine: the numpy↔JAX seam, owned end to end.

The gossip wire ends where the accelerator begins.  Before this package
existed the seam was ad-hoc: a single-slot jitted lerp in
``parallel/tcp.py``, a ``jnp.asarray`` upload per frame, a full
``np.asarray`` readback per round, and every sparse codec densified on
the host before a dense merge.  The engine replaces all of it with
three parts (docs/device.md):

- :mod:`~dpwa_tpu.device.handoff` — zero-copy host→device ingestion of
  decoded frame views (dlpack pointer adoption at 64-byte alignment,
  ``device_put`` fallback) and the ONE sanctioned d2h readback.
- :mod:`~dpwa_tpu.device.kernels` — one fused decode+lerp kernel per
  codec family (dense f32, bf16-upcast, int8 dequant, top-k scatter,
  shard dynamic-slice, batched k-fold), each compiled once per shape
  key in an explicit LRU'd :class:`~dpwa_tpu.device.kernels.JitCache`
  and bit-identical to the host reference merge.
- :mod:`~dpwa_tpu.device.engine` / :mod:`~dpwa_tpu.device.replica` —
  the :class:`~dpwa_tpu.device.engine.MergeEngine` dispatcher plus the
  :class:`~dpwa_tpu.device.replica.DeviceReplica` handle that keeps the
  replica device-resident between rounds with a lazy, versioned host
  mirror (readback only at publish/checkpoint/trust boundaries).

Importable without a JAX backend — jax loads inside the kernel
builders and handoff calls, never at module scope (the contract shared
with ``parallel/tcp.py``).
"""

from dpwa_tpu.device.engine import (
    MergeEngine,
    default_engine,
    device_snapshot,
    reset_device_stats,
)
from dpwa_tpu.device.kernels import JitCache
from dpwa_tpu.device.replica import DeviceReplica

__all__ = [
    "MergeEngine",
    "JitCache",
    "DeviceReplica",
    "default_engine",
    "device_snapshot",
    "reset_device_stats",
]
