#!/usr/bin/env python
"""Wire-compression bench: what bf16/int8 buy on the reference fabric.

The gossip bottleneck on the reference's own substrate is the TCP wire
(BASELINE.md: ~0.15–0.3 GB/s localhost; real DCN/WAN is slower still).
`protocol.wire_dtype` compresses the SHIPPED replica — this bench
measures, for one full-model exchange (publish → fetch → merge) over
real sockets at each wire format:

- bytes on the wire (header + payload, exact),
- end-to-end wall time per exchange INCLUDING codec cost (quantize at
  publish, dequantize at fetch — compression is not free on the host,
  and localhost bandwidth is cheap, so the wall-time win here is a
  LOWER bound on what a real network shows),
- effective model-bytes-per-second (model f32 size / wall time): the
  number a user cares about — how fast does a full replica effectively
  cross the fabric.

Writes ``artifacts/wire_compression.json``.  Host-only (TCP path); runs
identically with or without the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Host-only bench, but the import chain (config -> schedules) touches
# jax — pin the CPU backend before anything can take the chip (it adds
# nothing to a TCP-wire measurement).
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

from dpwa_tpu.config import make_local_config
from dpwa_tpu.parallel.tcp import TcpTransport, _frame, _INT8_CHUNKED
from dpwa_tpu.ops.quantize import encode_int8_payload


def wire_bytes(vec: np.ndarray, wire_dtype: str, seed: int) -> int:
    """Exact framed size of one published replica at this wire format."""
    if wire_dtype == "int8":
        payload = encode_int8_payload(vec, seed, 1.0, 0)
        return len(_frame(payload, 1.0, 0.0, _INT8_CHUNKED))
    if wire_dtype == "bf16":
        import ml_dtypes

        return len(_frame(vec.astype(ml_dtypes.bfloat16), 1.0, 0.0))
    return len(_frame(vec, 1.0, 0.0))


def bench_wire(wire_dtype: str, n_elems: int, iters: int, seed: int) -> dict:
    cfg = make_local_config(
        2, base_port=0, schedule="ring", wire_dtype=wire_dtype, seed=seed
    )
    ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
    for t in ts:
        for i, other in enumerate(ts):
            t.set_peer_port(i, other.port)
    try:
        rng = np.random.default_rng(seed)
        vecs = [
            rng.standard_normal(n_elems).astype(np.float32) for _ in range(2)
        ]
        # Warm both directions (connect path, codec warmup), and leave
        # node1's published blob in place: node1's OWN publish cost runs
        # in node1's process in a real cluster, so it stays OUTSIDE
        # node0's timed path (the fetched content is whatever the
        # partner last served — its bytes, not its codec time, are what
        # node0's round pays for).
        for i, t in enumerate(ts):
            t.publish(vecs[i], 0.0, 0.0)
        ts[0].exchange(vecs[0], 1.0, 0.0, 0)

        t0 = time.perf_counter()
        clock = 1.0
        for it in range(iters):
            clock += 1.0
            # One gossip round as node0 experiences it: publish its own
            # replica (1x codec), fetch the partner's blob (wire bytes),
            # decode, merge.
            merged, alpha, partner = ts[0].exchange(
                vecs[0], clock, 0.0, it
            )
        dt = (time.perf_counter() - t0) / iters
        model_bytes = vecs[0].nbytes
        wb = wire_bytes(vecs[0], wire_dtype, cfg.protocol.seed)
        wb_f32 = wire_bytes(vecs[0], "f32", cfg.protocol.seed)
        return {
            "wire_dtype": wire_dtype,
            "model_mb_f32": round(model_bytes / 1e6, 2),
            "wire_bytes_per_replica": wb,
            "compression_vs_f32": round(wb_f32 / wb, 2),
            "exchange_ms": round(dt * 1e3, 2),
            "effective_model_mbps": round(model_bytes / dt / 1e6, 1),
            "iters": iters,
        }
    finally:
        for t in ts:
            t.close()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--elems", type=int, default=25_000_000,
                    help="model size in f32 elements (default 100 MB)")
    ap.add_argument("--iters", type=int, default=12)
    args = ap.parse_args()

    rows = []
    for wd in ("f32", "bf16", "int8"):
        row = bench_wire(wd, args.elems, args.iters, seed=0)
        print(f"[{wd}] {row['exchange_ms']} ms/exchange, "
              f"{row['wire_bytes_per_replica']/1e6:.1f} MB on wire, "
              f"{row['effective_model_mbps']} MB(model)/s",
              file=sys.stderr, flush=True)
        rows.append(row)

    # Codec-only throughput + the crossover figure: compression strictly
    # wins wall time once the network moves bytes slower than
    # bytes_saved / codec_seconds.  Localhost (~GB/s) sits far above the
    # int8 crossover; any real DCN/WAN link sits below it.
    from dpwa_tpu.ops.quantize import (
        decode_int8_payload, encode_int8_payload,
    )

    vec = np.random.default_rng(0).standard_normal(args.elems).astype(
        np.float32
    )
    encode_int8_payload(vec, 0, 0.0, 0)  # warm
    t0 = time.perf_counter()
    payload = encode_int8_payload(vec, 0, 1.0, 0)
    t_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    decode_int8_payload(payload)
    t_dec = time.perf_counter() - t0
    bytes_saved = vec.nbytes - payload.nbytes
    codec = {
        "int8_encode_gbps": round(vec.nbytes / t_enc / 1e9, 2),
        "int8_decode_gbps": round(vec.nbytes / t_dec / 1e9, 2),
        "int8_crossover_network_mbps": round(
            bytes_saved / (t_enc + t_dec) / 1e6, 1
        ),
        "note": (
            "on any link slower than int8_crossover_network_mbps the "
            "int8 wire is a strict wall-time win; bytes-on-wire is a "
            "3.9x win at any speed"
        ),
    }
    print(f"[codec] enc {codec['int8_encode_gbps']} GB/s, dec "
          f"{codec['int8_decode_gbps']} GB/s, crossover "
          f"{codec['int8_crossover_network_mbps']} MB/s",
          file=sys.stderr, flush=True)

    f32 = rows[0]
    out = {
        "experiment": "wire_compression",
        "note": (
            "one full exchange (publish incl. codec -> fetch incl. "
            "decode -> merge) of a 100 MB f32 replica over localhost "
            "TCP per wire format.  Localhost bandwidth is cheap, so "
            "wall-time wins here are a LOWER bound on a real network, "
            "where the byte reduction converts ~1:1 into time; "
            "bytes-on-wire is exact either way"
        ),
        "rows": rows,
        "codec": codec,
        "speedup_vs_f32": {
            r["wire_dtype"]: round(
                f32["exchange_ms"] / r["exchange_ms"], 2
            )
            for r in rows
        },
    }
    path = os.path.join(REPO, "artifacts", "wire_compression.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
