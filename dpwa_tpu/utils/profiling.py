"""Tracing / profiling (SURVEY.md §5: absent in the reference; first-class
here).

- :func:`trace` — context manager around ``jax.profiler`` producing a
  TensorBoard-loadable trace of the training loop (XLA ops, collectives,
  host callbacks).
- :func:`measure_exchange_bandwidth` — the GB/s/chip counter around the
  averaging collective, the headline metric (BASELINE.json:2), for users
  to run against their own models.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """``with trace("/tmp/trace"):`` — profile everything inside."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def measure_exchange_bandwidth(
    transport,
    params,
    meta,
    *,
    iters: int = 20,
    start_step: int = 0,
) -> dict:
    """Time `transport.exchange` and report per-chip averaging bandwidth.

    Accounting per SURVEY.md §7: one exchange moves 2 × payload bytes per
    peer (receive partner's copy, write the merge)."""
    from dpwa_tpu.utils.pytree import tree_size_bytes

    payload = tree_size_bytes(jax.tree.map(lambda v: v[0], params))
    merged, _ = transport.exchange(params, meta, start_step)  # warmup
    jax.block_until_ready(merged)
    t0 = time.perf_counter()
    cur = params
    for i in range(iters):
        cur, _ = transport.exchange(cur, meta, start_step + i)
    jax.block_until_ready(cur)
    dt = time.perf_counter() - t0
    per_chip_bytes = 2 * payload * iters
    return {
        "payload_bytes": payload,
        "iters": iters,
        "seconds": dt,
        "gbps_per_chip": per_chip_bytes / dt / 1e9,
    }
