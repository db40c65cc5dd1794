"""Host↔device handoff: the ONE place frame bytes cross the seam.

The zero-copy frame path (docs/transport.md) delivers decoded payload
views straight out of the receive ring; this module moves them onto the
accelerator without re-materializing them on the way.  On the CPU
backend ``to_device`` ingests a host array via dlpack when the view is
eligible — C-contiguous and 64-byte aligned (``ALIGN``), which
:class:`~dpwa_tpu.parallel.ingest.BufferRing` guarantees for
lease-offset-0 views — so the crossing is a pointer adoption.  On an
accelerator backend there is no host pointer to adopt (dlpack of a numpy
view would leave the array on the CPU backend, away from the replica in
HBM), so every view crosses by ``jax.device_put``: one host→device copy,
never ``bytes -> ndarray -> device`` twice.  Ineligible views (unaligned
codec offsets, non-contiguous slices) take ``device_put`` too, and the
split is tallied so ``wire_snapshot()`` can show when frames stopped
crossing clean.

Ownership contract (the dlpack half of the lease rules in
``parallel/ingest.py``): a zero-copy device array ALIASES the host
buffer, so the source must be immutable-by-convention and stay alive
until every consuming dispatch has run.  Decoded frame views satisfy
both — the lease was detached (the views' refcounts keep the buffer
alive, dlpack's capsule holds the view) and nothing writes a received
frame.  Never hand ``to_device`` a buffer you intend to recycle.

``to_host`` is the sanctioned readback: the merge engine keeps the
replica device-resident between rounds, and host floats exist only at
the boundaries that genuinely need them — publish-encode, checkpoint,
trust/guard screening (``docs/device.md`` "Readback boundaries").  Every
other ``np.asarray(device_array)`` in a merge-path module is a lint
error (``device-host-roundtrip``).

Pure-python tallies only at import: jax loads inside the functions, so
the module is importable without a backend.
"""

from __future__ import annotations

import threading

import numpy as np

# dlpack-eligible alignment: XLA's CPU client adopts external buffers at
# 64-byte alignment (cacheline); anything less is copied on import.
ALIGN = 64

_LOCK = threading.Lock()
_H2D_ZERO_COPY = 0
_H2D_COPIED = 0
_H2D_BYTES = 0
_D2H_READBACKS = 0
_D2H_BYTES = 0


def dlpack_eligible(arr: np.ndarray) -> bool:
    """True when ``arr`` can cross by pointer adoption: C-contiguous
    with a 64-byte-aligned base.  Codec views at odd intra-frame offsets
    (int8 q-blocks after scale tables, top-k value blocks after index
    lists) legitimately fail this — they cross via ``device_put``."""
    return bool(
        arr.flags.c_contiguous and arr.ctypes.data % ALIGN == 0
    )


def to_device(arr: np.ndarray):
    """Host array -> device array on the default device, crossing
    exactly once.  dlpack (pointer adoption) when the default backend is
    the CPU and the view is eligible, else ``jax.device_put`` (one copy
    onto the default device); either way the caller's view is never
    routed through an intermediate ``bytes``/``ndarray``."""
    global _H2D_ZERO_COPY, _H2D_COPIED, _H2D_BYTES
    import jax
    import jax.numpy as jnp

    zero_copy = False
    if jax.default_backend() == "cpu" and dlpack_eligible(arr):
        try:
            out = jnp.from_dlpack(arr)
            zero_copy = True
        except (TypeError, ValueError, RuntimeError):
            # The CPU client refuses this dtype/layout over dlpack (bf16
            # views): staging copy.
            out = jax.device_put(arr)
    else:
        out = jax.device_put(arr)
    with _LOCK:
        _H2D_BYTES += int(arr.nbytes)
        if zero_copy:
            _H2D_ZERO_COPY += 1
        else:
            _H2D_COPIED += 1
    return out


def to_host(dev) -> np.ndarray:
    """Device array -> host f32 ndarray: THE sanctioned readback.

    On the CPU backend this is a view adoption; on a real device it is
    the one d2h DMA a publish/checkpoint boundary pays.  Callers hold
    the result immutable — on CPU it aliases the (immutable) device
    buffer."""
    global _D2H_READBACKS, _D2H_BYTES
    # dpwalint: ignore[device-host-roundtrip] -- this IS the readback boundary every other merge-path module must route through
    out = np.asarray(dev)
    with _LOCK:
        _D2H_READBACKS += 1
        _D2H_BYTES += int(out.nbytes)
    return out


def handoff_stats() -> dict:
    """Snapshot for ``device_snapshot()``: crossings by kind + bytes."""
    with _LOCK:
        total = _H2D_ZERO_COPY + _H2D_COPIED
        return {
            "h2d_transfers": total,
            "h2d_zero_copy": _H2D_ZERO_COPY,
            "h2d_zero_copy_frac": (
                (_H2D_ZERO_COPY / total) if total else 0.0
            ),
            "h2d_bytes": _H2D_BYTES,
            "d2h_readbacks": _D2H_READBACKS,
            "d2h_bytes": _D2H_BYTES,
        }


def reset_handoff_stats() -> None:
    """Test hook: zero the process-wide tally."""
    global _H2D_ZERO_COPY, _H2D_COPIED, _H2D_BYTES
    global _D2H_READBACKS, _D2H_BYTES
    with _LOCK:
        _H2D_ZERO_COPY = _H2D_COPIED = _H2D_BYTES = 0
        _D2H_READBACKS = _D2H_BYTES = 0
