"""The merge engine: codec-aware fused merges over a device replica.

:class:`MergeEngine` owns the numpy↔JAX seam for the gossip merge —
``TcpTransport.exchange_on_device`` is a thin caller.  Every ``merge_*`` method takes the device-resident local
replica plus a decoded frame's RAW parts (dense view, u16 bf16 view,
int8 q+scale views, top-k index/value pair, shard slice), crosses them
through :mod:`~dpwa_tpu.device.handoff` exactly once, and dispatches
one fused kernel from :mod:`~dpwa_tpu.device.kernels` — compiled once
per ``(family, shape, …)`` key in the engine's :class:`JitCache` and
bit-identical to the host reference merge (the acceptance contract;
tests/test_device_engine.py proves it per codec × shard-k × trailer).

``fold()`` is the batched multi-peer form: k pending dense frames merge
in ONE dispatch as k in-graph sequential lerps — same bits as k
separate ``merge_dense`` calls, minus k−1 dispatch+sync round-trips.

Counters (dispatches, rounds, cache hits/misses) feed
``wire_snapshot()``'s device columns; the module-level
:func:`default_engine` is process-wide for the same reason the receive
ring is — transports share one device and the health columns are
per-process.  Nothing here imports jax at module scope.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from dpwa_tpu.device import handoff, kernels
from dpwa_tpu.ops.quantize import TopkPayload, int8_payload_views
from dpwa_tpu.ops.shard import ShardPayload

try:  # bf16 wire views — ml_dtypes ships with jax
    import ml_dtypes
except ImportError:  # pragma: no cover - ml_dtypes is a jax dependency
    ml_dtypes = None


class MergeEngine:
    """Fused decode+lerp merges, one jit cache, one stats plane."""

    def __init__(self, cache_capacity: int = kernels.DEFAULT_CACHE_CAPACITY):
        self.cache = kernels.JitCache(cache_capacity)
        self._lock = threading.Lock()
        self._dispatches = 0
        self._rounds = 0
        self._fold_frames = 0

    # -- dispatch accounting -------------------------------------------
    def _note_dispatch(self, frames: int = 1) -> None:
        with self._lock:
            self._dispatches += 1
            if frames > 1:
                self._fold_frames += frames

    def note_round(self) -> None:
        """One gossip round consumed the engine (merged or skipped) —
        the denominator of ``device_dispatches_per_round``."""
        with self._lock:
            self._rounds += 1

    @staticmethod
    def _t(alpha: float) -> np.float32:
        # f32 at the trace boundary: ``1.0 - t`` must round in f32 or
        # the kernel drifts one ulp off the native axpy reference.
        return np.float32(alpha)

    # -- kernel families -----------------------------------------------
    def merge_dense(self, local_dev, remote: np.ndarray, alpha: float):
        """Full-vector f32 lerp (dense wire, decoded int8 frames)."""
        n = int(remote.size)
        fn = self.cache.get(
            ("dense", n), lambda: kernels.build_dense(n)
        )
        self._note_dispatch()
        return fn(local_dev, handoff.to_device(remote), self._t(alpha))

    def merge_bf16(self, local_dev, remote_bf16: np.ndarray, alpha: float):
        """bf16 wire frame: crosses as its raw u16 view, upcast fused
        in-kernel — the host upcast copy disappears."""
        raw = remote_bf16.view(np.uint16)
        n = int(raw.size)
        fn = self.cache.get(("bf16", n), lambda: kernels.build_bf16(n))
        self._note_dispatch()
        return fn(local_dev, handoff.to_device(raw), self._t(alpha))

    def merge_int8(self, local_dev, payload: np.ndarray, alpha: float):
        """int8-chunked wire body: fused dequant-lerp straight off the
        payload's q/scale views — no dense f32 remote, host or device."""
        n, scales, q = int8_payload_views(payload)
        chunks = int(scales.size)
        fn = self.cache.get(
            ("int8", n, chunks), lambda: kernels.build_int8(n, chunks)
        )
        self._note_dispatch()
        return fn(
            local_dev,
            handoff.to_device(q),
            handoff.to_device(scales),
            self._t(alpha),
        )

    def merge_topk(
        self, local_dev, indices: np.ndarray, values: np.ndarray,
        alpha: float,
    ):
        """Top-k frame: scatter-lerp over the support; the densified
        estimate exists only inside the fused program."""
        n = int(local_dev.shape[0])
        k = int(indices.size)
        fn = self.cache.get(
            ("topk", n, k), lambda: kernels.build_topk(n, k)
        )
        self._note_dispatch()
        return fn(
            local_dev,
            handoff.to_device(np.ascontiguousarray(indices)),
            handoff.to_device(np.ascontiguousarray(values)),
            self._t(alpha),
        )

    def merge_shard(
        self, local_dev, lo: int, est_slice: np.ndarray, alpha: float
    ):
        """Shard frame with a dense (or already-densified) slice
        estimate: dynamic-slice lerp over ``[lo, lo+m)`` — the k−1
        unshipped slices never leave the device, bit-identical."""
        n = int(local_dev.shape[0])
        m = int(est_slice.size)
        fn = self.cache.get(
            ("shard", n, m), lambda: kernels.build_shard(n, m)
        )
        self._note_dispatch()
        return fn(
            local_dev,
            handoff.to_device(np.ascontiguousarray(est_slice)),
            np.int32(lo),
            self._t(alpha),
        )

    def merge_shard_topk(
        self, local_dev, lo: int, m: int, indices: np.ndarray,
        values: np.ndarray, alpha: float,
    ):
        """Top-k within a shard: scatter into the slice in-graph, lerp,
        splice — no densified slice on either side of the seam."""
        n = int(local_dev.shape[0])
        k = int(indices.size)
        fn = self.cache.get(
            ("shard_topk", n, m, k),
            lambda: kernels.build_shard_topk(n, m, k),
        )
        self._note_dispatch()
        return fn(
            local_dev,
            handoff.to_device(np.ascontiguousarray(indices)),
            handoff.to_device(np.ascontiguousarray(values)),
            np.int32(lo),
            self._t(alpha),
        )

    def merge(self, local_dev, remote, alpha: float):
        """Dispatch a decoded frame by its payload type — the thin-
        caller entry the :meth:`~dpwa_tpu.parallel.tcp.TcpTransport`-side
        substrates share."""
        if isinstance(remote, TopkPayload):
            return self.merge_topk(
                local_dev, remote.indices, remote.values, alpha
            )
        if isinstance(remote, ShardPayload):
            lo, hi = remote.bounds
            inner = remote.inner
            if isinstance(inner, TopkPayload):
                return self.merge_shard_topk(
                    local_dev, lo, hi - lo, inner.indices, inner.values,
                    alpha,
                )
            return self.merge_shard(local_dev, lo, inner, alpha)
        if (
            ml_dtypes is not None
            and remote.dtype == np.dtype(ml_dtypes.bfloat16)
        ):
            return self.merge_bf16(local_dev, remote, alpha)
        return self.merge_dense(local_dev, remote, alpha)

    def fold(
        self, local_dev, remotes: Sequence[np.ndarray],
        alphas: Sequence[float],
    ):
        """Batched multi-peer fold: ``x ← lerp(…lerp(x, r_0, t_0)…,
        r_{k-1}, t_{k-1})`` in ONE dispatch, bit-identical to the k
        sequential merges it replaces (in-graph unroll keeps the op
        order)."""
        if len(remotes) != len(alphas):
            raise ValueError(
                f"fold got {len(remotes)} frames but {len(alphas)} alphas"
            )
        if not remotes:
            return local_dev
        k = len(remotes)
        n = int(remotes[0].size)
        fn = self.cache.get(
            ("fold", n, k), lambda: kernels.build_fold(n, k)
        )
        ts = np.array([float(a) for a in alphas], dtype=np.float32)
        devs = [handoff.to_device(r) for r in remotes]
        self._note_dispatch(frames=k)
        return fn(local_dev, handoff.to_device(ts), *devs)

    # -- observability -------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-ready device-plane state (``wire_snapshot()``'s device
        columns + docs/device.md's accounting)."""
        cache = self.cache.snapshot()
        with self._lock:
            dispatches = self._dispatches
            rounds = self._rounds
            fold_frames = self._fold_frames
        out = {
            "jit_cache_hits": cache["hits"],
            "jit_cache_misses": cache["misses"],
            "jit_cache_entries": cache["entries"],
            "device_dispatches": dispatches,
            "device_rounds": rounds,
            "device_dispatches_per_round": (
                round(dispatches / rounds, 4) if rounds else 0.0
            ),
            "fold_frames": fold_frames,
        }
        out.update(handoff.handoff_stats())
        return out


# Process-wide engine: transports share one device plane, and the
# device health columns are per-process (the receive-ring precedent).
_DEFAULT_LOCK = threading.Lock()
_DEFAULT_ENGINE: Optional[MergeEngine] = None


def default_engine() -> MergeEngine:
    global _DEFAULT_ENGINE
    with _DEFAULT_LOCK:
        if _DEFAULT_ENGINE is None:
            _DEFAULT_ENGINE = MergeEngine()
        return _DEFAULT_ENGINE


def device_snapshot() -> dict:
    """The default engine's snapshot — zeros before first use, never a
    jax import (``wire_snapshot()`` must stay backend-free)."""
    return default_engine().snapshot()


def reset_device_stats() -> None:
    """Test hook: fresh default engine + zeroed handoff tally."""
    global _DEFAULT_ENGINE
    with _DEFAULT_LOCK:
        _DEFAULT_ENGINE = None
    handoff.reset_handoff_stats()
