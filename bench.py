#!/usr/bin/env python
"""Headline benchmark: pairwise-averaging bandwidth, TPU vs reference CPU/TCP.

Measures the hot operation of the framework — the gossip exchange
``x ← (1−α)·x + α·x_partner`` — on the accelerator, against the
reference-equivalent baseline (flattened float32 vector over a localhost TCP
socket + CPU axpy merge; SURVEY.md §3.2 hot spots).  BASELINE.json:2 names
this (pairwise-avg GB/s/chip) the metric; the north-star target is ≥50× the
CPU/TCP path (BASELINE.json:5).

Accounting (SURVEY.md §7 "honest GB/s/chip"): one exchange moves
2 × vector-bytes per participating peer (receive the partner's vector, write
the merge).  With N real devices the exchange is the actual ``ppermute``
collective; on a single chip it is the stacked virtual-peer merge (same math,
measures the on-chip HBM path).  Both are reported per chip.  Pools padded
with self-pairs are counted by their *actual* pair count, so padded DMA rows
never inflate the figure (exact for perfect matchings, conservative
otherwise).

One process for each chip: the main process never imports JAX.  It runs
the CPU-side legs in subprocesses pinned to the CPU backend and then ONE
device child, which takes whatever platform JAX selected.  There is no CPU
fallback and no replay: when the device leg fails, or lands on a CPU, the
run prints no result line and exits non-zero.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": "GB/s/chip", "vs_baseline": ...,
   "platform": "tpu", "device_kind": ..., "device_count": ...,
   "tcp_baseline_gbps": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def bench_device(d: int, n_peers: int, iters: int) -> tuple[float, dict]:
    """Averaging bandwidth on the platform JAX selected: (GB/s per chip,
    what it ran on and through which path)."""
    import jax
    import jax.numpy as jnp

    from dpwa_tpu.utils.launch import enable_compile_cache
    from dpwa_tpu.utils.profiling import timed_loop

    enable_compile_cache()
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    log(f"device leg on {info}")

    if len(devices) >= n_peers:
        # Real multi-device path: the actual transport collective.
        from dpwa_tpu.config import make_local_config
        from dpwa_tpu.interpolation import PeerMeta
        from dpwa_tpu.parallel.ici import IciTransport
        from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding

        cfg = make_local_config(n_peers, schedule="ring")
        mesh = make_mesh(cfg, devices=devices[:n_peers])
        transport = IciTransport(cfg, mesh=mesh)
        sh = peer_sharding(mesh)
        x = jax.device_put(
            jnp.ones((n_peers, d), jnp.float32)
            * jnp.arange(n_peers, dtype=jnp.float32)[:, None],
            sh,
        )
        meta = PeerMeta(
            jnp.ones(n_peers, jnp.float32), jnp.ones(n_peers, jnp.float32)
        )
        per_iter, _ = timed_loop(
            lambda p, step: transport.exchange(p, meta, step)[0],
            {"v": x},
            iters,
            warmup=1,
        )
        info["path"] = "ici-exchange"
        # Per chip: each chip receives d*4 bytes and writes d*4 bytes.
        return 2 * d * 4 / per_iter / 1e9, info

    # Single-chip path: stacked virtual peers (SURVEY.md §7 note), ring
    # pairing resolved as data by the fused merge.  On TPU this is the
    # in-place pair kernel (pallas_pair_merge): one read + one write per
    # element — the traffic floor — with the pairing arriving as
    # scalar-prefetch data, so both ring phases share one compiled kernel.
    from dpwa_tpu.ops.merge import (
        involution_pairs,
        pairwise_merge,
        pallas_pair_merge,
    )
    from dpwa_tpu.parallel.schedules import _ring_even, _ring_odd

    pools = [_ring_even(n_peers), _ring_odd(n_peers)]
    alphas = jnp.full((n_peers,), 0.5, jnp.float32)

    x = jnp.ones((n_peers, d), jnp.float32) * jnp.arange(
        n_peers, dtype=jnp.float32
    )[:, None]

    if devices[0].platform == "tpu" and d % 1024 == 0:
        actual_pairs = [len(involution_pairs(p)[0]) for p in pools]
        n_pairs = max(actual_pairs)
        lr = [involution_pairs(p, pad_to=n_pairs) for p in pools]
        lefts = [jnp.asarray(l) for l, _ in lr]
        rights = [jnp.asarray(r) for _, r in lr]
        # 3D layout: the donated buffer aliases straight into the kernel
        # (a 2D buffer would pay a reshape copy every step).
        x = x.reshape(n_peers, d // 128, 128)
        per_iter, _ = timed_loop(
            lambda b, step: pallas_pair_merge(
                b, lefts[step % 2], rights[step % 2], alphas
            ),
            x,
            iters,
            warmup=2,
        )
        info["path"] = "pallas-pair-merge"
        # Honest accounting: count only the per-pool *actual* pairs over the
        # iteration sequence, each row read once + written once.  Pools
        # padded to max(n_pairs) do DMA the pad self-pair rows, but those
        # bytes are excluded here so padding can only understate GB/s.
        total_bytes = sum(
            2 * actual_pairs[step % 2] * 2 * d * 4 for step in range(iters)
        )
        return total_bytes / (per_iter * iters) / 1e9, info

    perms = jnp.asarray(np.stack(pools), jnp.int32)
    per_iter, _ = timed_loop(
        lambda b, step: pairwise_merge(b, perms[step % 2], alphas),
        x,
        iters,
        warmup=2,
    )
    info["path"] = "xla-merge"
    # All n virtual peers live on the one chip: it reads the permuted
    # partner vector and writes the merge for each -> 2*d*4 bytes per peer.
    return n_peers * 2 * d * 4 / per_iter / 1e9, info


TCP_LEG_CPU_BUDGET = 2


def pin_cpu_budget(n: int = TCP_LEG_CPU_BUDGET) -> bool:
    """Pin THIS process to a fixed budget of ``n`` CPUs.

    The TCP baseline is the denominator of ``vs_baseline``, and an
    unpinned leg wanders with scheduler placement (two transport
    threads plus the interpreter migrating across a big box produce
    run-to-run swings far larger than any real transport change).  The
    leg runs in its own subprocess (``--tcp-leg``), so the pin cannot
    leak into the device legs.  Returns True when the budget is in
    effect; False on platforms without ``sched_setaffinity``."""
    try:
        cpus = sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return False
    if len(cpus) <= n:
        return True  # already at or below budget
    try:
        os.sched_setaffinity(0, set(cpus[:n]))
    except OSError:
        return False
    return True


def bench_tcp(
    d: int, iters: int, timeout_ms: int = 10000, repeats: int = 3,
    warmups: int = 3,
) -> dict:
    """Reference-equivalent baseline: 2 peers, localhost TCP, CPU merge.

    Runs ``warmups`` throwaway exchanges (socket buffers, allocator
    pools, the adaptive-deadline estimator, and the receive ring all
    start cold — the first exchanges of a fresh pair measure setup, not
    steady state), then ``repeats`` independent measurement passes of
    ``iters`` exchanges each.  The headline ``gbps`` is the median of
    the per-pass medians — one noisy pass (GC, a cron wakeup) cannot
    drag it — and ``spread_iqr_frac`` (IQR of the per-pass GB/s over
    their median) quantifies how much the passes disagreed, so
    :func:`tcp_gate` can refuse to trust a wobbling baseline instead of
    letting it silently inflate ``vs_baseline``."""
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.parallel.tcp import TcpTransport

    cfg = make_local_config(
        2, base_port=0, schedule="ring", timeout_ms=timeout_ms
    )
    ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
    for t in ts:
        for i, other in enumerate(ts):
            t.set_peer_port(i, other.port)
    try:
        vecs = [
            np.full(d, float(i), np.float32) for i in range(2)
        ]
        warmups = max(1, warmups)
        for w in range(warmups):
            for i, t in enumerate(ts):
                t.publish(vecs[i], w, 0)
            for i, t in enumerate(ts):
                t.exchange(vecs[i], w, 0, w)

        medians = []
        for rep in range(max(1, repeats)):
            durations = []
            for it in range(iters):
                step = warmups + rep * iters + it
                for i, t in enumerate(ts):
                    t.publish(vecs[i], step, 0)
                results = [None, None]

                def run(i):
                    results[i] = ts[i].exchange(vecs[i], step, 0, 0)

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=run, args=(i,))
                    for i in range(2)
                ]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                durations.append(time.perf_counter() - t0)
                assert results[0][1] != 0.0, "TCP exchange failed"
            medians.append(float(np.median(durations)))
        # Per peer per exchange: receive d*4 bytes + write the merge d*4.
        rep_gbps = [2 * d * 4 / m / 1e9 for m in medians]
        gbps = float(np.median(rep_gbps))
        q25, q75 = np.percentile(rep_gbps, [25, 75])
        return {
            "gbps": gbps,
            "rep_gbps": [round(g, 4) for g in rep_gbps],
            "spread_iqr_frac": (
                round(float(q75 - q25) / gbps, 4) if gbps > 0 else None
            ),
            "warmups": int(warmups),
            "repeats": int(max(1, repeats)),
            "iters": int(iters),
        }
    finally:
        for t in ts:
            t.close()


TCP_GATE_WINDOW = 8
TCP_GATE_REL_TOL = 0.5
# A baseline whose measurement passes disagree by more than this
# (IQR / median of the per-pass GB/s) is not a baseline — the verdict
# becomes "unstable" and vs_baseline is suspect regardless of where the
# headline number happened to land inside the band.
TCP_GATE_SPREAD_TOL = 0.25

# Measurement-methodology version stamped on every history entry this
# bench writes (``bench_methodology``).  The gates below only median
# samples carrying the SAME stamp: the TCP leg's numbers moved ~18x when
# the CPU-budget pinning landed, and a window that mixed pinned with
# unpinned samples compared the current run against a median dominated
# by the old methodology — the verdict read "improved" forever.  Bump
# this whenever a harness change (pinning, socket options, timer source)
# shifts what the same machine measures; entries WITHOUT the field are
# the unpinned era and never comparable to anything current.
#   v2: TCP leg runs under pin_cpu_budget (fixed CPU budget), hier leg
#       counts frames from the engine accounting.
BENCH_METHODOLOGY = 2


def tcp_gate(
    history: list,
    current_gbps,
    window: int = TCP_GATE_WINDOW,
    rel_tol: float = TCP_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
    spread_iqr_frac=None,
    spread_tol: float = TCP_GATE_SPREAD_TOL,
) -> dict:
    """Regression gate for the TCP baseline (pure; tests/test_fleet.py).

    ``history`` is the parsed ``artifacts/bench_history.jsonl`` entries;
    the gate takes the last ``window`` runs that recorded a live
    ``tcp_baseline_gbps`` *under the same measurement methodology*
    (``bench_methodology`` stamp — like compared with like only),
    medians them, and classifies the current measurement against a
    symmetric relative band.  The verdict is recorded in the output (not
    a hard failure): a "regressed" TCP baseline silently *inflates*
    ``vs_baseline``, so the 21x-127x headline is only trusted when the
    gate says "ok".  Until two comparable samples exist the verdict is
    ``no_data`` — never a judgement against an incomparable era.

    ``spread_iqr_frac`` is :func:`bench_tcp`'s own dispersion measure
    (IQR of the per-pass GB/s over their median).  When it exceeds
    ``spread_tol`` the verdict is ``unstable`` BEFORE any band
    comparison: a measurement whose passes disagree by >25% can land
    anywhere in the band by luck, so neither "ok" nor "regressed" would
    mean anything."""
    samples = [
        float(e["tcp_baseline_gbps"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("tcp_baseline_gbps"), (int, float))
        and not isinstance(e.get("tcp_baseline_gbps"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_gbps": round(median, 3) if median is not None else None,
        "current_gbps": (
            round(float(current_gbps), 3)
            if current_gbps is not None else None
        ),
        "spread_iqr_frac": (
            round(float(spread_iqr_frac), 4)
            if spread_iqr_frac is not None else None
        ),
        "spread_tol": float(spread_tol),
    }
    if (
        current_gbps is not None
        and spread_iqr_frac is not None
        and float(spread_iqr_frac) > spread_tol
    ):
        gate["verdict"] = "unstable"
        return gate
    if current_gbps is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_gbps)
    if cur < median * (1.0 - rel_tol):
        gate["verdict"] = "regressed"
    elif cur > median * (1.0 + rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


HIER_GATE_WINDOW = 8
HIER_GATE_REL_TOL = 0.5


def bench_hier(
    total_peers: int,
    island_sizes,
    rounds: int,
    target_rel: float,
    seed: int = 0,
) -> dict:
    """Simulated-island sweep (docs/hierarchy.md): island_size ×
    island_count at FIXED total peers, against the flat ring baseline.

    Each point drives a :class:`~dpwa_tpu.hier.engine.HierGossipEngine`
    episode at the same seed/rounds as the flat baseline and reports the
    wide-area frame multiplier (flat frames / hier frames — the whole
    point of the hierarchy) plus rounds-to-target, so the record shows
    whether the frame saving cost any convergence.  Counts come from the
    engine's frame accounting, not layout arithmetic — measured, never
    assumed (the wire-sweep discipline)."""
    from dpwa_tpu.hier.engine import HierGossipEngine
    from dpwa_tpu.hier.topology import Topology

    flat = HierGossipEngine(total_peers, seed=seed).run(
        rounds, target_rel=target_rel
    )
    legs: dict = {}
    for size in island_sizes:
        size = int(size)
        if size < 2 or total_peers % size or total_peers // size < 2:
            continue
        count = total_peers // size
        res = HierGossipEngine(
            total_peers, seed=seed, topology=Topology.uniform(count, size)
        ).run(rounds, target_rel=target_rel)
        legs[f"{count}x{size}"] = {
            "island_count": count,
            "island_size": size,
            "wide_frames": res["wide_frames"],
            "intra_frames": res["intra_frames"],
            "wide_multiplier": round(
                flat["wide_frames"] / max(res["wide_frames"], 1), 3
            ),
            "rounds_to_target": res["rounds_to_target"],
            "final_rel_rms": round(res["final_rel_rms"], 9),
        }
    mults = [leg["wide_multiplier"] for leg in legs.values()]
    return {
        "total_peers": int(total_peers),
        "rounds": int(rounds),
        "target_rel": float(target_rel),
        "seed": int(seed),
        "flat": {
            "wide_frames": flat["wide_frames"],
            "rounds_to_target": flat["rounds_to_target"],
            "final_rel_rms": round(flat["final_rel_rms"], 9),
        },
        "legs": legs,
        "wide_multiplier_min": min(mults) if mults else None,
    }


def hier_gate(
    history: list,
    current_mult,
    window: int = HIER_GATE_WINDOW,
    rel_tol: float = HIER_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
) -> dict:
    """Regression gate for the hier sweep's WORST wide-frame multiplier
    (pure; mirrors :func:`tcp_gate`, including the like-with-like
    ``bench_methodology`` filter): a refactor that quietly starts
    fetching wide-area frames for non-leaders shows up here as a
    "regressed" verdict against the recent history medians."""
    samples = [
        float(e["hier"]["wide_multiplier_min"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("hier"), dict)
        and isinstance(
            e["hier"].get("wide_multiplier_min"), (int, float)
        )
        and not isinstance(e["hier"].get("wide_multiplier_min"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_mult": round(median, 3) if median is not None else None,
        "current_mult": (
            round(float(current_mult), 3)
            if current_mult is not None else None
        ),
    }
    if current_mult is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_mult)
    if cur < median * (1.0 - rel_tol):
        gate["verdict"] = "regressed"
    elif cur > median * (1.0 + rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


MERGE_GATE_WINDOW = 8
MERGE_GATE_REL_TOL = 0.5
MERGE_GATE_SPREAD_TOL = 0.25


def merge_gate(
    history: list,
    current_gbps,
    window: int = MERGE_GATE_WINDOW,
    rel_tol: float = MERGE_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
    spread_iqr_frac=None,
    spread_tol: float = MERGE_GATE_SPREAD_TOL,
) -> dict:
    """Regression gate for the fused merge leg (the ``tcp_gate``
    pattern, keyed on ``merge_fused_gbps``): median of the last
    ``window`` same-methodology history samples, symmetric relative
    band, ``unstable`` short-circuit when the run's own per-iteration
    dispersion exceeds ``spread_tol`` — a measurement whose iterations
    disagree by >25% can land anywhere in the band by luck.  The
    verdict rides in the merge-leg record (not a hard failure) exactly
    like ``tcp_gate``'s does in the headline record."""
    samples = [
        float(e["merge_fused_gbps"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("merge_fused_gbps"), (int, float))
        and not isinstance(e.get("merge_fused_gbps"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_gbps": round(median, 3) if median is not None else None,
        "current_gbps": (
            round(float(current_gbps), 3)
            if current_gbps is not None else None
        ),
        "spread_iqr_frac": (
            round(float(spread_iqr_frac), 4)
            if spread_iqr_frac is not None else None
        ),
        "spread_tol": float(spread_tol),
    }
    if (
        current_gbps is not None
        and spread_iqr_frac is not None
        and float(spread_iqr_frac) > spread_tol
    ):
        gate["verdict"] = "unstable"
        return gate
    if current_gbps is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_gbps)
    if cur < median * (1.0 - rel_tol):
        gate["verdict"] = "regressed"
    elif cur > median * (1.0 + rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


def read_bench_history(path: str, max_lines: int = 512) -> list:
    """Parse the tail of ``bench_history.jsonl``; [] when absent."""
    entries: list = []
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()[-max_lines:]
    except OSError:
        return entries
    for ln in lines:
        try:
            entries.append(json.loads(ln))
        except json.JSONDecodeError:
            continue
    return entries


WIRE_SWEEP_CODECS = (
    ("f32", {"wire_dtype": "f32"}),
    ("bf16", {"wire_dtype": "bf16"}),
    ("int8", {"wire_dtype": "int8"}),
    ("topk_0.1", {"wire_codec": "topk", "topk_fraction": 0.1}),
    ("topk_0.05", {"wire_codec": "topk", "topk_fraction": 0.05}),
)


def bench_wire(d: int, iters: int, timeout_ms: int = 10000) -> dict:
    """BENCH_r06 sweep: on-wire bytes + exchange wall per codec, plus an
    overlap leg measuring how much fetch wall hides under a compute
    stand-in.

    2 peers on localhost, driven sequentially (node0 then node1 per
    round) so timings measure codec work, not thread scheduling.  Bytes
    come from each transport's ``wire_snapshot()`` — a tally of the
    frames actually published — not from layout arithmetic, so the
    reported reduction ratios are measured, never assumed.
    """
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.parallel.tcp import TcpTransport

    def ring(base_port=0, **kw):
        cfg = make_local_config(
            2, base_port=base_port, schedule="ring", timeout_ms=timeout_ms, **kw
        )
        ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
        for t in ts:
            for i, other in enumerate(ts):
                t.set_peer_port(i, other.port)
        return ts

    rng = np.random.default_rng(0)
    base = [rng.standard_normal(d).astype(np.float32) for _ in range(2)]

    def drive(ts, sleep_s=0.0):
        vecs = [b.copy() for b in base]
        durs = []
        for it in range(iters):
            for i, t in enumerate(ts):
                t.publish(vecs[i], it, 0.0)
            t0 = time.perf_counter()
            for i, t in enumerate(ts):
                merged, alpha, _ = t.exchange(vecs[i], it, 0.0, it)
                if alpha != 0.0:
                    vecs[i] = np.asarray(merged, np.float32)
            durs.append(time.perf_counter() - t0)
            if sleep_s:
                # Compute stand-in: the window the prefetch pipeline is
                # supposed to hide the NEXT round's fetch under.
                time.sleep(sleep_s)
        return durs

    legs = {}
    for name, kw in WIRE_SWEEP_CODECS:
        ts = ring(**kw)
        try:
            durs = drive(ts)
            snap = ts[0].wire_snapshot()
            legs[name] = {
                "wire_bytes_per_frame": round(
                    snap["wire_bytes"] / max(snap["frames"], 1), 1
                ),
                "compression_ratio": snap["compression_ratio"],
                # Median wall of one node0+node1 exchange pair, halved to
                # a per-exchange figure.
                "exchange_ms": round(float(np.median(durs)) * 1e3 / 2, 3),
            }
        finally:
            for t in ts:
                t.close()
    f32_b = legs["f32"]["wire_bytes_per_frame"]
    int8_b = legs["int8"]["wire_bytes_per_frame"]
    for leg in legs.values():
        leg["reduction_vs_f32"] = round(f32_b / leg["wire_bytes_per_frame"], 2)
        leg["reduction_vs_int8"] = round(
            int8_b / leg["wire_bytes_per_frame"], 2
        )

    out = {"d": d, "iters": iters, "legs": legs}

    # Overlap leg: dense f32 with the prefetch pipeline on, compute
    # stand-in sized from the dense exchange median so there is a real
    # window for the background fetch to hide under.
    compute_s = max(legs["f32"]["exchange_ms"] / 1e3, 0.002)
    ts = ring(overlap_prefetch=True)
    try:
        drive(ts, sleep_s=compute_s)
        ov = ts[0].wire_snapshot().get("overlap") or {}
        out["overlap"] = {
            "compute_stand_in_ms": round(compute_s * 1e3, 3),
            "hidden_frac": ov.get("hidden_frac"),
            "occupancy": ov.get("occupancy"),
            "prefetched": ov.get("prefetched"),
            "straddled": ov.get("straddled"),
        }
    finally:
        for t in ts:
            t.close()

    # Observability leg (BENCH_r07): dense f32 with tracing + sketch on.
    # ``obs.trace`` forces the Python Rx server so serve spans can be
    # timed, so the overhead baseline must be a dense f32 leg on the
    # SAME server — against the native-Rx f32 leg the delta would mostly
    # measure the server swap, not tracing.  The tracer's per-stage
    # medians are the span breakdown; the wall delta vs the Python-Rx
    # baseline is the measured tracing + sketch overhead (acceptance
    # budget: <5% of round wall).
    import os

    prev_rx = os.environ.get("DPWA_NATIVE_RX")
    os.environ["DPWA_NATIVE_RX"] = "0"
    try:
        # Localhost exchange walls drift by a few percent over seconds
        # with system load — the same order as the overhead being
        # measured — so the two legs are ITERATION-INTERLEAVED: both
        # rings stay live (distinct ports) and each iteration drives one
        # round on the baseline ring, then one on the obs ring, pairing
        # walls measured milliseconds apart.  The median of per-
        # iteration deltas is immune to drift on any slower timescale;
        # back-to-back full drives per leg were observed to report
        # anywhere from 0% to 11% for the same build.
        obs_iters = max(iters, 40)
        base_ts = ring()
        # Detectors + flight ring armed on top of trace/sketch: the <5%
        # budget covers the FULL obs plane, incident tick included.
        # Flight dumps land in a temp dir, not the repo.
        import tempfile

        obs_ts = ring(
            base_port=2,
            obs={
                "trace": True,
                "sketch": True,
                "incidents": True,
                "recorder": True,
                "recorder_path": os.path.join(
                    tempfile.mkdtemp(prefix="dpwa-bench-flight-"),
                    "flight-{me}.jsonl",
                ),
            },
        )
        try:
            base_vecs = [b.copy() for b in base]
            obs_vecs = [b.copy() for b in base]

            def one_round(ts, vecs, it):
                for i, t in enumerate(ts):
                    t.publish(vecs[i], it, 0.0)
                t0 = time.perf_counter()
                for i, t in enumerate(ts):
                    merged, alpha, _ = t.exchange(vecs[i], it, 0.0, it)
                    if alpha != 0.0:
                        vecs[i] = np.asarray(merged, np.float32)
                return time.perf_counter() - t0

            # Warmup: the sketch's one-time sign generation (a JAX
            # compile) lands here, off the clock.
            for it in range(5):
                one_round(base_ts, base_vecs, it)
                one_round(obs_ts, obs_vecs, it)
            deltas, bases = [], []
            for it in range(5, 5 + obs_iters):
                b = one_round(base_ts, base_vecs, it)
                o = one_round(obs_ts, obs_vecs, it)
                bases.append(b)
                deltas.append(o - b)
            summary = obs_ts[0].tracer.stage_summary()
        finally:
            for t in base_ts + obs_ts:
                t.close()
        # Pair wall halved to the per-exchange figure the codec legs use.
        mid = float(np.median(deltas)) * 1e3 / 2
        pyrx_ms = round(float(np.median(bases)) * 1e3 / 2, 3)
        obs_ms = round(pyrx_ms + max(mid, 0.0), 3)
    finally:
        if prev_rx is None:
            os.environ.pop("DPWA_NATIVE_RX", None)
        else:
            os.environ["DPWA_NATIVE_RX"] = prev_rx
    out["spans"] = {
        "exchange_ms": obs_ms,
        "pyrx_baseline_ms": pyrx_ms,
        "stage_median_ms": {
            stage: info["median_ms"] for stage, info in summary.items()
        },
        "obs_overhead_pct": (
            round(max(obs_ms - pyrx_ms, 0.0) / pyrx_ms * 100, 2)
            if pyrx_ms
            else None
        ),
    }
    return out


# Shard counts for the sharded-wire sweep: k=1 is the unsharded
# baseline every reduction is measured against.
SHARD_SWEEP_KS = (1, 2, 4, 8)


def bench_shard(
    d: int, iters: int, ks=SHARD_SWEEP_KS, timeout_ms: int = 10000
) -> dict:
    """Sharded-wire sweep (docs/wire.md): bytes/frame at ``shard.k`` in
    ``ks``, for the dense f32 wire and composed with the top-k codec.

    Same discipline as :func:`bench_wire`: 2 peers on localhost driven
    sequentially, bytes from each transport's ``wire_snapshot()`` frame
    tally — measured, never layout arithmetic.  ``reduction_vs_k1`` is
    within a codec family (f32 k=4 vs f32 k=1, topk k=4 vs topk k=1),
    so it isolates the shard saving from the codec's own ratio;
    ``reduction_floor_frac`` is the worst ``reduction_vs_k1 / k`` over
    k>1 legs — the acceptance bar is >= 0.9 (the preamble is the only
    overhead, so anything lower means a leg stopped shipping slices)."""
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.parallel.tcp import TcpTransport

    def ring(**kw):
        cfg = make_local_config(
            2, base_port=0, schedule="ring", timeout_ms=timeout_ms, **kw
        )
        ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
        for t in ts:
            for i, other in enumerate(ts):
                t.set_peer_port(i, other.port)
        return ts

    rng = np.random.default_rng(0)
    base = [rng.standard_normal(d).astype(np.float32) for _ in range(2)]

    def drive(ts):
        vecs = [b.copy() for b in base]
        durs = []
        for it in range(iters):
            for i, t in enumerate(ts):
                t.publish(vecs[i], it, 0.0)
            t0 = time.perf_counter()
            for i, t in enumerate(ts):
                merged, alpha, _ = t.exchange(vecs[i], it, 0.0, it)
                if alpha != 0.0:
                    vecs[i] = np.asarray(merged, np.float32)
            durs.append(time.perf_counter() - t0)
        return durs

    families = (
        ("f32", {}),
        ("topk", {"wire_codec": "topk", "topk_fraction": 0.05}),
    )
    legs: dict = {}
    for fam, kw in families:
        for k in ks:
            ts = ring(shard={"k": int(k)}, **kw)
            try:
                durs = drive(ts)
                snap = ts[0].wire_snapshot()
                leg = {
                    "k": int(k),
                    "codec": snap["codec"],
                    "wire_bytes_per_frame": round(
                        snap["wire_bytes"] / max(snap["frames"], 1), 1
                    ),
                    "compression_ratio": snap["compression_ratio"],
                    "exchange_ms": round(
                        float(np.median(durs)) * 1e3 / 2, 3
                    ),
                }
                sh = snap.get("shard")
                if sh is not None:
                    leg["coverage"] = sh["coverage"]
                legs[f"{fam}_k{k}"] = leg
            finally:
                for t in ts:
                    t.close()
    floor = None
    for fam, _ in families:
        b1 = legs[f"{fam}_k1"]["wire_bytes_per_frame"]
        for k in ks:
            leg = legs[f"{fam}_k{k}"]
            leg["reduction_vs_k1"] = round(
                b1 / leg["wire_bytes_per_frame"], 2
            )
            if k > 1:
                frac = leg["reduction_vs_k1"] / k
                floor = frac if floor is None else min(floor, frac)
    return {
        "d": int(d),
        "iters": int(iters),
        "ks": [int(k) for k in ks],
        "legs": legs,
        "reduction_floor_frac": (
            round(floor, 3) if floor is not None else None
        ),
    }


# Held-peer counts for the serve-leg capacity sweep (ISSUE 10): the
# C10K-style question "how many concurrently held connections can the Rx
# server carry while still serving a fresh fetch?", asked at ring sizes
# up to the 256-peer target.
SERVE_SWEEP = (16, 64, 256)


def bench_serve(frame_floats: int, fps_seconds: float) -> dict:
    """Rx serve leg: threaded thread-per-connection vs reactor event loop.

    Two sub-measurements per server, both against the SAME default
    operating envelope each server ships with (threaded:
    ``max_connections=32``; reactor: ``reactor_max_connections=1024``)
    — the comparison is between deployable configurations, not between
    artificially equalized ones:

    - **frames/sec**: 16 fetcher threads hammer one published
      ``frame_floats``-float blob for ``fps_seconds``; sustained
      served-frame throughput.
    - **capacity sweep**: for each N in ``SERVE_SWEEP``, N simulated
      peers connect and HOLD their connections (no bytes sent — the
      idle phase of a slow peer), then one fresh probe fetch runs.  A
      point is *sustained* when all N holds stay admitted AND the probe
      is served.  ``capacity_conns`` is the largest sustained N; the
      thread-per-connection server tops out at its thread cap while the
      reactor carries the whole sweep on one loop thread.

    Token pacing is opened up (everything arrives from 127.0.0.1, so
    the per-host bucket would otherwise throttle the bench itself, not
    model reality); connection caps and eviction stay live.
    """
    from dpwa_tpu.config import FlowctlConfig
    from dpwa_tpu.parallel.reactor import ReactorPeerServer
    from dpwa_tpu.parallel.tcp import PeerServer, fetch_blob_ex

    import socket as _socket

    fc = FlowctlConfig(token_rate=1e9, token_burst=1e9)
    makers = {
        "threaded": lambda: PeerServer("127.0.0.1", 0, flowctl=fc),
        "reactor": lambda: ReactorPeerServer("127.0.0.1", 0, flowctl=fc),
    }
    vec = np.zeros(frame_floats, np.float32)

    def frames_leg(make) -> dict:
        srv = make()
        try:
            srv.publish(vec, 1.0, 0.0)
            nworkers = 16
            stop_at = time.perf_counter() + fps_seconds
            counts = [0] * nworkers
            errors = [0] * nworkers

            def worker(i: int) -> None:
                while time.perf_counter() < stop_at:
                    res = fetch_blob_ex("127.0.0.1", srv.port, 2000)
                    if res[0] is not None:
                        counts[i] += 1
                    else:
                        errors[i] += 1

            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(nworkers)
            ]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            return {
                "frames": sum(counts),
                "fetch_errors": sum(errors),
                "wall_s": round(wall, 3),
                "frames_per_s": round(sum(counts) / max(wall, 1e-9), 1),
            }
        finally:
            srv.close()

    def held_count(socks) -> int:
        """Connections the server still holds open: a shed connection has
        a busy frame (or plain EOF/RST) waiting, a held one has nothing."""
        held = 0
        for s in socks:
            s.setblocking(False)
            try:
                s.recv(16)  # bytes or b"" -> shed/closed
            except (BlockingIOError, InterruptedError):
                held += 1
            except OSError:
                pass  # reset -> shed
        return held

    def capacity_leg(make) -> dict:
        points = {}
        capacity = 0
        for n in SERVE_SWEEP:
            srv = make()
            socks = []
            try:
                srv.publish(vec, 1.0, 0.0)
                for _ in range(n):
                    try:
                        socks.append(
                            _socket.create_connection(
                                ("127.0.0.1", srv.port), timeout=2.0
                            )
                        )
                    except OSError:
                        break
                # Let accept + admission settle (the reactor drains
                # accepts in 64-connection batches per loop tick).
                time.sleep(0.3)
                held = held_count(socks)
                probe = fetch_blob_ex("127.0.0.1", srv.port, 2000)
                probe_ok = probe[0] is not None
                sustained = held == n and probe_ok
                points[str(n)] = {
                    "held": held,
                    "probe_ok": probe_ok,
                    "sustained": sustained,
                }
                if sustained:
                    capacity = max(capacity, n)
            finally:
                for s in socks:
                    try:
                        s.close()
                    except OSError:
                        pass
                srv.close()
        return {"points": points, "capacity_conns": capacity}

    servers = {}
    for name, make in makers.items():
        log(f"serve leg [{name}]: frames/sec x{fps_seconds:.1f}s ...")
        res = frames_leg(make)
        log(f"serve leg [{name}]: capacity sweep {list(SERVE_SWEEP)} ...")
        res.update(capacity_leg(make))
        servers[name] = res

    thr_cap = servers["threaded"]["capacity_conns"]
    rx_cap = servers["reactor"]["capacity_conns"]
    return {
        "frame_bytes": frame_floats * 4,
        "fps_seconds": fps_seconds,
        "sweep": list(SERVE_SWEEP),
        "servers": servers,
        "capacity_ratio": (
            round(rx_cap / thr_cap, 2) if thr_cap else None
        ),
    }


# --- Async gossip leg (docs/async.md): barrier-free vs lock-step ---
#
# 4 peers on localhost with ONE chaos-shaped trickling straggler (bytes
# flow, but at a rate that makes every fetch of its replica blow the
# round budget).  The lock-step leg pays the straggler on every round
# that pairs an honest peer with it; the async leg keeps merging
# whatever frames have landed and charges the straggler's lag to
# staleness damping instead of the honest peers' wall clock.  The
# headline is the honest peers' straggler-unthrottled speedup:
# lock-step p99 round wall over async p99.
ASYNC_GATE_WINDOW = 8
ASYNC_GATE_REL_TOL = 0.5
ASYNC_SWEEP_PEERS = 4
ASYNC_SWEEP_FLOATS = 4096


def async_gate(
    history: list,
    current_speedup,
    window: int = ASYNC_GATE_WINDOW,
    rel_tol: float = ASYNC_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
) -> dict:
    """Regression gate for the async leg's straggler-unthrottled speedup
    (pure; mirrors :func:`tcp_gate`, including the like-with-like
    ``bench_methodology`` filter).  A refactor that quietly re-couples
    the round loop to the slowest peer — a blocking join on the fetch
    slot, a barrier hiding in the merge path — collapses the speedup
    toward 1x and shows up here as "regressed" against recent medians.
    The band is wide (``rel_tol`` 0.5): the lock-step numerator is a
    timeout-dominated wall, stable, but the async denominator is a
    scheduler-sensitive few-ms figure."""
    samples = [
        float(e["async_straggler_speedup"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("async_straggler_speedup"), (int, float))
        and not isinstance(e.get("async_straggler_speedup"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_speedup": round(median, 3) if median is not None else None,
        "current_speedup": (
            round(float(current_speedup), 3)
            if current_speedup is not None else None
        ),
    }
    if current_speedup is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_speedup)
    if cur < median * (1.0 - rel_tol):
        gate["verdict"] = "regressed"
    elif cur > median * (1.0 + rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


TUNE_GATE_WINDOW = 8
TUNE_GATE_REL_TOL = 0.5


def tune_gate(
    history: list,
    current_speedup,
    window: int = TUNE_GATE_WINDOW,
    rel_tol: float = TUNE_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
) -> dict:
    """Regression gate for the self-tuning wire's unthrottle ratio
    (pure; the :func:`async_gate` mold, including the like-with-like
    ``bench_methodology`` filter).  The ratio is the static-f32 leg's
    settled-regime p50 round wall over the tuned leg's — how much of
    the shaped links' throttle the per-link controller sheds by
    walking the codec ladder instead of timing out.  A change that
    stops evidence reaching the controller (the observe feed, the
    publish-side plan, the error-feedback reset) collapses the ratio
    toward 1x and shows up here as "regressed" against recent medians.
    The band is wide (``rel_tol`` 0.5): the numerator is a
    timeout-dominated wall, stable, but the denominator is a
    scheduler-sensitive few-ms figure."""
    samples = [
        float(e["tune_unthrottle"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("tune_unthrottle"), (int, float))
        and not isinstance(e.get("tune_unthrottle"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_speedup": round(median, 3) if median is not None else None,
        "current_speedup": (
            round(float(current_speedup), 3)
            if current_speedup is not None else None
        ),
    }
    if current_speedup is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_speedup)
    if cur < median * (1.0 - rel_tol):
        gate["verdict"] = "regressed"
    elif cur > median * (1.0 + rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


FLEET_GATE_WINDOW = 8
FLEET_GATE_REL_TOL = 0.5
# The leg's fixed view block: the O(sample) claim is about THESE bounds
# holding flat while N grows 16x, so the bench pins them rather than
# exposing knobs that would make history entries incomparable.
FLEET_LEG_VIEW = dict(
    enabled=True, active_size=8, passive_size=32, digest_sample=16,
    state_cap=64, shuffle_every=8,
)


def fleet_gate(
    history: list,
    current_bytes,
    window: int = FLEET_GATE_WINDOW,
    rel_tol: float = FLEET_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
) -> dict:
    """Regression gate for the fleet leg's per-node resident state
    (pure; mirrors :func:`tcp_gate`'s median-window + like-with-like
    ``bench_methodology`` filter, with the band inverted: resident
    BYTES are a cost, so drifting up is the regression).  A refactor
    that sneaks an O(N) map back into a control plane — a snapshot that
    iterates ``range(n_peers)``, a per-peer dict that stops pruning on
    eviction — inflates the largest-N residency figure and shows up
    here as "regressed" against recent medians."""
    samples = [
        float(e["fleet_resident_bytes"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("fleet_resident_bytes"), (int, float))
        and not isinstance(e.get("fleet_resident_bytes"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "median_bytes": round(median, 1) if median is not None else None,
        "current_bytes": (
            round(float(current_bytes), 1)
            if current_bytes is not None else None
        ),
    }
    if current_bytes is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_bytes)
    if cur > median * (1.0 + rel_tol):
        gate["verdict"] = "regressed"
    elif cur < median * (1.0 - rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


TRAIN_GATE_WINDOW = 8
TRAIN_GATE_REL_TOL = 0.5


def train_gate(
    history: list,
    current_steps,
    leg_ok: bool,
    window: int = TRAIN_GATE_WINDOW,
    rel_tol: float = TRAIN_GATE_REL_TOL,
    methodology: int = BENCH_METHODOLOGY,
) -> dict:
    """Regression gate for the end-to-end training leg, keyed on the
    clean leg's ``train_steps_to_target`` (pure; the ``fleet_gate``
    inverted-band pattern — steps to target loss are a cost, so
    drifting UP is the regression).  Two layers:

    - ``leg_ok`` is the leg's own chaos-certification verdict (every
      acceptance bool in ``LegResult.verdict``); a failed leg is
      ``"failed"`` outright — no history median can excuse a run that
      did not converge or whose incident plane misbehaved;
    - the metric band then judges time-to-quality drift against the
      last ``window`` same-methodology history samples, so a merge
      regression that slows convergence without breaking acceptance
      still surfaces here."""
    samples = [
        float(e["train_steps_to_target"])
        for e in history
        if isinstance(e, dict)
        and e.get("record") == "bench"
        and e.get("bench_methodology") == methodology
        and isinstance(e.get("train_steps_to_target"), (int, float))
        and not isinstance(e.get("train_steps_to_target"), bool)
    ][-int(window):]
    median = float(np.median(samples)) if samples else None
    gate = {
        "samples": len(samples),
        "window": int(window),
        "rel_tol": float(rel_tol),
        "methodology": int(methodology),
        "leg_ok": bool(leg_ok),
        "median_steps": (
            round(median, 1) if median is not None else None
        ),
        "current_steps": (
            round(float(current_steps), 1)
            if current_steps is not None else None
        ),
    }
    if not leg_ok:
        gate["verdict"] = "failed"
        return gate
    if current_steps is None or len(samples) < 2:
        gate["verdict"] = "no_data"
        return gate
    cur = float(current_steps)
    if cur > median * (1.0 + rel_tol):
        gate["verdict"] = "regressed"
    elif cur < median * (1.0 - rel_tol):
        gate["verdict"] = "improved"
    else:
        gate["verdict"] = "ok"
    return gate


def bench_fleet(
    peer_counts,
    rounds: int = 24,
    seed: int = 0,
) -> dict:
    """Orchestrator soak across ``peer_counts`` under a fixed partial
    view (docs/membership.md): per-node resident control-plane bytes
    and digest bytes/frame, measured while the fleet churns.

    The acceptance shape is O(sample)/O(state_cap): the residency and
    frame figures at N=4096 must sit in the same band as at N=256
    (``resident_scaling`` ~1x while ``peer_scaling`` is 16x), because
    every per-peer map is capped and every frame is sampled.  Residency
    comes from :meth:`FleetOrchestrator.residency_snapshot` — measured
    ``sys.getsizeof`` sums over the live containers, never layout
    arithmetic (the wire-sweep discipline)."""
    from dpwa_tpu.config import HealthConfig, MembershipConfig, ViewConfig
    from dpwa_tpu.fleet.orchestrator import FleetOrchestrator
    from dpwa_tpu.fleet.schedule import ChurnSpec

    view = ViewConfig(**FLEET_LEG_VIEW)
    legs: dict = {}
    for n in sorted(int(n) for n in peer_counts):
        spec = ChurnSpec(
            seed=seed,
            leave_probability=0.002,
            join_probability=0.2,
            cohort_every=8,
            cohort_max=max(2, n // 512),
            restart_every=10,
            min_live=max(2, (7 * n) // 8),
        )
        orch = FleetOrchestrator(
            n, spec, dim=8,
            health=HealthConfig(jitter_rounds=0),
            membership=MembershipConfig(
                dead_after_quarantines=2,
                dead_gossip_rounds=4,
                view=view,
            ),
        )
        t0 = time.perf_counter()
        res = orch.run(int(rounds))
        wall = time.perf_counter() - t0
        ep = res.episode
        live = [p for p in range(n) if orch.nodes[p].alive]
        stride = max(1, len(live) // 64)
        snaps = [orch.residency_snapshot(p) for p in live[::stride]]
        resident = sorted(s["resident_bytes"] for s in snaps)
        legs[f"n{n}"] = {
            "n_peers": int(n),
            "rounds": int(rounds),
            "resident_bytes_median": int(np.median(resident)),
            "resident_bytes_max": int(ep["view_max_resident_bytes"]),
            "tracked_max": int(ep["view_max_tracked"]),
            "digest_entries_max": int(ep["view_max_digest_entries"]),
            "digest_bytes_max": int(ep["max_digest_bytes"]),
            "round_wall_ms": round(wall / max(1, rounds) * 1e3, 3),
            "final_live": int(ep["final_live"]),
        }
    ns = sorted(int(n) for n in peer_counts)
    lo, hi = legs[f"n{ns[0]}"], legs[f"n{ns[-1]}"]
    return {
        "view": dict(FLEET_LEG_VIEW),
        "legs": legs,
        # 16x more peers should cost ~1x more per-node state: the
        # headline pair the gate and the README table quote.
        "peer_scaling": round(ns[-1] / max(1, ns[0]), 4),
        "resident_scaling": round(
            hi["resident_bytes_max"] / max(1, lo["resident_bytes_max"]), 4
        ),
        "digest_scaling": round(
            hi["digest_bytes_max"] / max(1, lo["digest_bytes_max"]), 4
        ),
        "fleet_resident_bytes": hi["resident_bytes_max"],
        "fleet_digest_bytes": hi["digest_bytes_max"],
    }


def bench_async(
    d: int = ASYNC_SWEEP_FLOATS,
    iters: int = 24,
    peers: int = ASYNC_SWEEP_PEERS,
    timeout_ms: int = 400,
    trickle_bytes_per_s: float = 2048.0,
    compute_ms: float = 30.0,
) -> dict:
    """Lock-step vs barrier-free rounds under a trickling straggler.

    Both legs run the SAME topology and fault schedule: ``peers`` nodes
    on localhost, ring schedule, with the last peer trickle-shaped for
    the whole run (bytes flow at ``trickle_bytes_per_s`` — far too slow
    to land a ``d``-float frame inside ``timeout_ms``, the honest-but-
    overloaded shape from docs/flowctl.md).  Each node drives its own
    thread so the lock-step leg exhibits the real coupling: every round
    that pairs an honest peer with the straggler stalls for the fetch
    budget.  The async leg (``protocol.async_rounds``) publishes and
    moves on; frames merge when they land, damped by staleness.

    ``compute_ms`` is the per-round compute stand-in (the bench_wire
    overlap-leg pattern), slept identically in BOTH legs: without it the
    async leg would sprint through every round before any fetch could
    land and "win" while merging nothing.  The sleep is excluded from
    the reported walls — it models the training step the round loop is
    supposed to hide the wire under, not round cost.

    Reported walls are the per-round exchange times of the HONEST peers
    only (the straggler's own wall is shaped by chaos, not by the round
    loop), p50/p99 over all honest rounds.  ``straggler_speedup`` is
    the lock-step p99 over the async p99 — how much of the straggler's
    throttle the async loop removed from peers that were never slow."""
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.parallel.tcp import TcpTransport

    straggler = peers - 1
    chaos = {
        "enabled": True,
        "trickle_windows": ((straggler, 0, iters),),
        "trickle_bytes_per_s": float(trickle_bytes_per_s),
    }

    def ring(**kw):
        cfg = make_local_config(
            peers, base_port=0, schedule="ring",
            timeout_ms=timeout_ms, chaos=chaos, **kw
        )
        ts = [TcpTransport(cfg, f"node{i}") for i in range(peers)]
        for t in ts:
            for i, other in enumerate(ts):
                t.set_peer_port(i, other.port)
        return ts

    rng = np.random.default_rng(0)
    base = [rng.standard_normal(d).astype(np.float32) for _ in range(peers)]

    def drive(ts):
        walls: list = [[] for _ in range(peers)]
        vecs = [b.copy() for b in base]

        def run_node(i, t):
            for it in range(iters):
                t.publish(vecs[i], float(it), 0.0)
                if compute_ms:
                    time.sleep(compute_ms / 1e3)
                t0 = time.perf_counter()
                merged, alpha, _ = t.exchange(vecs[i], float(it), 0.0, it)
                walls[i].append(time.perf_counter() - t0)
                if alpha != 0.0:
                    vecs[i] = np.asarray(merged, np.float32)

        threads = [
            threading.Thread(target=run_node, args=(i, t), daemon=True)
            for i, t in enumerate(ts)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return walls, vecs

    def leg(**kw):
        ts = ring(**kw)
        try:
            t0 = time.perf_counter()
            walls, vecs = drive(ts)
            total_s = time.perf_counter() - t0
            honest = [
                w for i, ws in enumerate(walls)
                if i != straggler for w in ws
            ]
            stack = np.stack(vecs)
            mean = stack.mean(axis=0)
            rel_rms = float(
                np.sqrt(np.mean((stack - mean) ** 2))
                / (np.sqrt(np.mean(mean ** 2)) + 1e-12)
            )
            out = {
                "p50_ms": round(
                    float(np.percentile(honest, 50)) * 1e3, 3
                ),
                "p99_ms": round(
                    float(np.percentile(honest, 99)) * 1e3, 3
                ),
                "total_s": round(total_s, 3),
                "final_rel_rms": round(rel_rms, 6),
            }
            eng = getattr(ts[0], "async_engine", None)
            if eng is not None:
                for t in ts:
                    t.async_engine.join_inflight(timeout_s=2.0)
                snaps = [t.async_engine.snapshot() for t in ts]
                out["async_merges"] = sum(s["merges"] for s in snaps)
                out["async_stale_drops"] = sum(
                    s["stale_drops"] for s in snaps
                )
                out["async_shed"] = sum(s["shed"] for s in snaps)
            return out
        finally:
            for t in ts:
                t.close()

    lock_leg = leg()
    async_leg = leg(async_rounds={"enabled": True})
    speedup = round(lock_leg["p99_ms"] / max(async_leg["p99_ms"], 1e-6), 3)
    return {
        "d": int(d),
        "iters": int(iters),
        "peers": int(peers),
        "timeout_ms": int(timeout_ms),
        "straggler": int(straggler),
        "trickle_bytes_per_s": float(trickle_bytes_per_s),
        "compute_ms": float(compute_ms),
        "lockstep": lock_leg,
        "async": async_leg,
        "straggler_speedup": speedup,
    }


def bench_tune(
    d: int = 4096,
    iters: int = 48,
    timeout_ms: int = 250,
    trickle_bytes_per_s: float = 8192.0,
    compute_ms: float = 5.0,
) -> dict:
    """Self-tuning wire vs the static codecs under mixed link shaping.

    Three legs run the SAME 4-peer localhost ring and the SAME fault
    schedule — a congested fabric with mixed link rates: peers 1 and 3
    trickle-shaped for the whole run (``trickle_bytes_per_s`` is far
    too slow to land a ``d``-float f32 frame inside ``timeout_ms``),
    peers 0 and 2 bandwidth-flapping (chaos ``bandwidth_windows``:
    each 6-round block independently draws clear — full-speed serving
    — or a shaped rate between "int8 fits" and "f32 almost fits").
    The legs differ only in the wire config: static f32 (the floor),
    static int8 (the best single static codec for this budget), and
    the per-link controller (``tune.enabled`` with a short window so
    the ladder walk fits the run).

    The shaping is fabric-symmetric on purpose.  The controller's
    evidence is fetch-side and its lever is publish-side, so a link
    heals when BOTH ends sit behind shaped egress: each observes slow
    fetches from the other and shrinks what it serves back.  A
    one-sided throttle (only the server shaped, the fetcher's own
    egress clear) leaves the shaped side blind — the anonymous fetch
    request carries no requester id, so failed serves cannot be
    attributed to a link — and that direction stays at the static
    config.  ``compute_ms`` is the per-round compute stand-in (the
    bench_async pattern), slept identically in every leg and excluded
    from the walls.

    Unlike bench_async, rounds here are BARRIERED: free-running
    threads let the shaped peers fall behind, after which cross-speed
    pairs fast-fail as STALE — milliseconds of wall, zero merges —
    and the static legs look fast while averaging nothing.  The
    barrier keeps every leg's clocks aligned so a shaped fetch pays
    its honest price (the timeout for an oversized frame, the real
    trickle transfer for one the ladder shrank to fit), and the
    settled walls compare wire behaviour, not clock skew.

    Reported per leg: p50/p99 round walls over the whole run and over
    the settled regime (the last third of rounds, after the ladder
    walk), merge count (rounds that actually folded a partner frame),
    and the disagreement trajectory (``rel_half_round`` — first round
    at half the starting rel — plus the endpoint).  ``tune_unthrottle``
    — the static-f32 settled p50 over the tuned settled p50 — is the
    gated headline; ``tune_vs_best_static`` is the same ratio against
    the int8 leg.  The rel columns keep the fidelity price visible: a
    static codec that lands averages at full density, while the
    controller's coarse rungs trade terminal precision for keeping
    every link merging — the walls and merge counts are the claim, the
    rel trajectory is the cost."""
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.parallel.tcp import TcpTransport

    peers = 4
    chaos = {
        "enabled": True,
        "trickle_windows": ((1, 0, iters), (3, 0, iters)),
        "trickle_bytes_per_s": float(trickle_bytes_per_s),
        "bandwidth_windows": ((0, 0, iters), (2, 0, iters)),
        "bandwidth_flap_probability": 0.75,
        "bandwidth_block_rounds": 6,
        "bandwidth_bps_min": 8192.0,
        "bandwidth_bps_max": 131072.0,
    }

    def ring(**kw):
        cfg = make_local_config(
            peers, base_port=0, schedule="ring",
            timeout_ms=timeout_ms, chaos=chaos,
            obs={"sketch": True, "sketch_k": 32}, **kw
        )
        ts = [TcpTransport(cfg, f"node{i}") for i in range(peers)]
        for t in ts:
            for i, other in enumerate(ts):
                t.set_peer_port(i, other.port)
        return ts

    rng = np.random.default_rng(0)
    base = [rng.standard_normal(d).astype(np.float32) for _ in range(peers)]

    def drive(ts):
        walls: list = [[] for _ in range(peers)]
        merges = [0] * peers
        vecs = [b.copy() for b in base]
        rel_curve: list = []
        # publish-barrier: everyone's round-N frame is up before anyone
        # fetches; done-barrier: all replicas settled so node 0 can
        # sample the round's disagreement; exit-barrier: nobody
        # overwrites the served frame with round N+1 while a trickled
        # serve is still feeding it out.
        enter = threading.Barrier(peers)
        done = threading.Barrier(peers)
        exit_ = threading.Barrier(peers)

        def rel_of(vs) -> float:
            stack = np.stack(vs)
            mean = stack.mean(axis=0)
            return float(
                np.sqrt(np.mean((stack - mean) ** 2))
                / (np.sqrt(np.mean(mean ** 2)) + 1e-12)
            )

        def run_node(i, t):
            for it in range(iters):
                t.publish(vecs[i], float(it), 0.0)
                enter.wait(timeout=60.0)
                if compute_ms:
                    time.sleep(compute_ms / 1e3)
                t0 = time.perf_counter()
                merged, alpha, _ = t.exchange(vecs[i], float(it), 0.0, it)
                walls[i].append(time.perf_counter() - t0)
                if alpha != 0.0:
                    merges[i] += 1
                    vecs[i] = np.asarray(merged, np.float32)
                done.wait(timeout=60.0)
                if i == 0:
                    rel_curve.append(round(rel_of(vecs), 6))
                exit_.wait(timeout=60.0)

        threads = [
            threading.Thread(target=run_node, args=(i, t), daemon=True)
            for i, t in enumerate(ts)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return walls, vecs, merges, rel_curve

    settled_from = iters - iters // 3

    def leg(**kw):
        ts = ring(**kw)
        try:
            t0 = time.perf_counter()
            walls, vecs, merges, rel_curve = drive(ts)
            total_s = time.perf_counter() - t0
            flat = [w for ws in walls for w in ws]
            settled = [w for ws in walls for w in ws[settled_from:]]
            stack = np.stack(vecs)
            mean = stack.mean(axis=0)
            rel_rms = float(
                np.sqrt(np.mean((stack - mean) ** 2))
                / (np.sqrt(np.mean(mean ** 2)) + 1e-12)
            )
            # First round at/below half the starting disagreement — a
            # horizon-free rounds-to-rel read alongside the endpoint.
            rel_half = None
            if rel_curve:
                target = rel_curve[0] / 2.0
                for r_i, r_v in enumerate(rel_curve):
                    if r_v <= target:
                        rel_half = r_i
                        break
            out = {
                "p50_ms": round(float(np.percentile(flat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(flat, 99)) * 1e3, 3),
                "settled_p50_ms": round(
                    float(np.percentile(settled, 50)) * 1e3, 3
                ),
                "settled_p99_ms": round(
                    float(np.percentile(settled, 99)) * 1e3, 3
                ),
                "merges": int(sum(merges)),
                "total_s": round(total_s, 3),
                "final_rel_rms": round(rel_rms, 6),
                "rel_half_round": rel_half,
            }
            snaps = [
                (t.health_snapshot() or {}).get("tune") for t in ts
            ]
            if any(s is not None for s in snaps):
                snaps = [s or {} for s in snaps]
                for key in (
                    "escalations", "backoffs", "sheds", "dwell_violations"
                ):
                    out[key] = sum(int(s.get(key) or 0) for s in snaps)
                out["final_rungs"] = sorted(
                    f"{i}->{p}:{st.get('codec')}"
                    for i, s in enumerate(snaps)
                    for p, st in sorted((s.get("links") or {}).items())
                )
            return out
        finally:
            for t in ts:
                t.close()

    f32_leg = leg()
    int8_leg = leg(wire_dtype="int8")
    tuned_leg = leg(tune={
        "enabled": True, "window": 2, "min_dwell_rounds": 1,
        "cooldown_rounds": 6, "jitter_rounds": 0,
    })
    unthrottle = round(
        f32_leg["settled_p50_ms"] / max(tuned_leg["settled_p50_ms"], 1e-6), 3
    )
    vs_best = round(
        int8_leg["settled_p50_ms"] / max(tuned_leg["settled_p50_ms"], 1e-6), 3
    )
    return {
        "d": int(d),
        "iters": int(iters),
        "peers": int(peers),
        "timeout_ms": int(timeout_ms),
        "trickle_bytes_per_s": float(trickle_bytes_per_s),
        "compute_ms": float(compute_ms),
        "fleet": {"trickled": [1, 3], "flapping": [0, 2]},
        "static_f32": f32_leg,
        "static_int8": int8_leg,
        "tuned": tuned_leg,
        "tune_unthrottle": unthrottle,
        "tune_vs_best_static": vs_best,
    }


# Frame sizes for the zero-copy leg: 4 KiB and ~392 KiB (the LoRA
# adapter-only exchange regime — dpwa_tpu/run/task.py's lora task ships
# d≈100K), then 16 MiB (a mid-size replica) and ~100 MB (the
# ResNet-50-scale default the headline bench ships).
COPY_SWEEP_FRAME_FLOATS = (
    1024, 100_352, 4 * 1024 * 1024, 24 * 1024 * 1024
)


def frame_label(nbytes: int) -> str:
    """Human frame-size label, KiB-resolved below 1 MiB — the integer
    ``>> 20`` label would collapse every small-frame cell onto "0MiB"
    and the sweep dict would silently keep only the last one."""
    if nbytes >= 1 << 20:
        return f"{nbytes >> 20}MiB"
    return f"{nbytes >> 10}KiB"


def _legacy_fetch_blob(host: str, port: int, timeout_ms: int = 20000):
    """The pre-ring fetch loop, preserved as the copy-leg baseline.

    This is what ``fetch_blob_full`` did before the zero-copy hot path
    landed: grow a bytearray chunk by chunk (every growth past the
    allocator's slack recopies the accumulated payload), then pay one
    more full-payload copy materializing ``bytes(buf)`` for
    ``np.frombuffer``.  Kept verbatim — same chunk cap, same EOF
    semantics — so the leg measures the copies, not a strawman."""
    import socket as _socket

    from dpwa_tpu.parallel.tcp import _HDR, _MAGIC, _REQ

    with _socket.create_connection(
        (host, port), timeout=timeout_ms / 1e3
    ) as sock:
        sock.settimeout(timeout_ms / 1e3)
        sock.sendall(_REQ)

        def recv_n(n: int) -> bytes:
            buf = bytearray()
            while len(buf) < n:
                chunk = sock.recv(min(1 << 20, n - len(buf)))
                if not chunk:
                    raise ConnectionError("peer closed mid-message")
                buf += chunk
            return bytes(buf)  # the full-payload copy the ring removed

        magic, version, code, clock, loss, nbytes = _HDR.unpack(
            recv_n(_HDR.size)
        )
        assert magic == _MAGIC and version == 1 and code == 0
        return np.frombuffer(recv_n(nbytes), np.float32), clock, loss


# Decode-allocation bound for the copy leg's sub-MiB cells: generous
# O(header + probe) slack (Python-object churn included), thousands of
# times below the replica-scale frames and still frame-size-independent.
COPY_ALLOC_CAP_BYTES = 64 * 1024


def bench_copy(
    sizes=COPY_SWEEP_FRAME_FLOATS, iters: int = 5, timeout_ms: int = 20000
) -> dict:
    """Zero-copy frame-path leg: old fetch loop vs the receive ring.

    For each frame size and each Rx server (threaded and reactor), one
    fetcher runs ``iters`` sequential f32-blob fetches down each path:

    - **legacy** — :func:`_legacy_fetch_blob`, the pre-ring chunk-grow
      loop with its ``bytes()`` materialization;
    - **zerocopy** — ``fetch_blob_full`` with an owned ring lease
      (``lease_box``, released per frame): ``recv_into`` straight into
      the pooled buffer, decode as a view, scatter-gather serve.

    Reports frames/sec and GB/s per path, the speedup, and — the
    O(header) proof — tracemalloc's peak allocation across one warmed
    zerocopy fetch (``decode_alloc_per_frame_bytes``), which stays
    thousands of times below the frame size when nothing copies."""
    from dpwa_tpu.config import FlowctlConfig
    from dpwa_tpu.health.detector import Outcome
    from dpwa_tpu.parallel.reactor import ReactorPeerServer
    from dpwa_tpu.parallel.tcp import PeerServer, fetch_blob_full

    fc = FlowctlConfig(token_rate=1e9, token_burst=1e9)
    makers = {
        "threaded": lambda: PeerServer("127.0.0.1", 0, flowctl=fc),
        "reactor": lambda: ReactorPeerServer("127.0.0.1", 0, flowctl=fc),
    }
    frames: dict = {}
    for floats in sizes:
        vec = np.zeros(int(floats), np.float32)
        servers: dict = {}
        for name, make in makers.items():
            srv = make()
            try:
                srv.publish(vec, 1.0, 0.0)

                def legacy_fetch():
                    got, _, _ = _legacy_fetch_blob(
                        "127.0.0.1", srv.port, timeout_ms
                    )
                    assert got.nbytes == vec.nbytes

                def zerocopy_fetch():
                    box: list = []
                    res, outcome, _, _, _, _ = fetch_blob_full(
                        "127.0.0.1", srv.port, timeout_ms, lease_box=box
                    )
                    assert outcome == Outcome.SUCCESS, outcome
                    assert res[0].nbytes == vec.nbytes
                    del res  # views die before the lease goes back
                    box[0].release()

                def timed(fn) -> float:
                    durs = []
                    for _ in range(max(1, iters)):
                        t0 = time.perf_counter()
                        fn()
                        durs.append(time.perf_counter() - t0)
                    return float(np.median(durs))

                # Warm both paths: TCP windows, allocator slack, and the
                # ring's size classes (probe + payload) all settle.
                legacy_fetch()
                zerocopy_fetch()
                legacy_dt = timed(legacy_fetch)
                zerocopy_dt = timed(zerocopy_fetch)
                tracemalloc.start()
                try:
                    zerocopy_fetch()
                    _, alloc_peak = tracemalloc.get_traced_memory()
                finally:
                    tracemalloc.stop()
                servers[name] = {
                    "legacy_fps": round(1.0 / legacy_dt, 2),
                    "legacy_gbps": round(vec.nbytes / legacy_dt / 1e9, 3),
                    "zerocopy_fps": round(1.0 / zerocopy_dt, 2),
                    "zerocopy_gbps": round(
                        vec.nbytes / zerocopy_dt / 1e9, 3
                    ),
                    "speedup": round(legacy_dt / zerocopy_dt, 2),
                    "decode_alloc_per_frame_bytes": int(alloc_peak),
                }
            finally:
                srv.close()
        frames[frame_label(vec.nbytes)] = {
            "frame_bytes": int(vec.nbytes),
            "servers": servers,
        }
    best = max(
        leg["speedup"]
        for fr in frames.values()
        for leg in fr["servers"].values()
    )
    # The O(header) acceptance for the small-frame (LoRA) regime: a
    # warmed zerocopy fetch's decode allocation must stay bounded by
    # header + probe bookkeeping — independent of frame size — or the
    # ring is quietly allocating per frame (the small-class waste the
    # KiB cells exist to expose).
    alloc_cap = COPY_ALLOC_CAP_BYTES
    small_ok = all(
        leg["decode_alloc_per_frame_bytes"] <= alloc_cap
        for fr in frames.values()
        if fr["frame_bytes"] < (1 << 20)
        for leg in fr["servers"].values()
    )
    return {
        "iters": int(iters),
        "sizes_floats": [int(s) for s in sizes],
        "frames": frames,
        "best_speedup": best,
        "alloc_cap_bytes": int(alloc_cap),
        "small_frame_alloc_ok": bool(small_ok),
    }


# Replica sizes for the merge leg: 16/48/96 MiB — mid-size replica up
# to the ResNet-50-scale default the headline bench ships.
MERGE_SWEEP_FRAME_FLOATS = (4 * 1024 * 1024, 12 * 1024 * 1024,
                            24 * 1024 * 1024)


def bench_merge(
    sizes=MERGE_SWEEP_FRAME_FLOATS,
    iters: int = 5,
    fold_ks=(2, 4, 8),
    topk_frac: float = 0.05,
    shard_k: int = 4,
) -> dict:
    """Device merge leg: the pre-engine merge path vs the fused kernels.

    For each replica size and codec family the **legacy** cell replays
    exactly what ``exchange_on_device`` did before the device engine
    landed (the single-slot ``_LERP_CACHE`` era): read the replica back
    to the host (``np.asarray`` — the per-exchange readback), decode or
    densify the frame host-side (int8 dequant, top-k densify, bf16
    upcast, shard merge on the host copy), then upload a FULL dense
    vector and lerp.  The **fused** cell is one ``MergeEngine``
    dispatch off the frame's raw wire views — no dense intermediate, no
    readback, the replica device-resident between rounds.

    GB/s is effective replica bandwidth: replica bytes maintained per
    merge over wall time, the same numerator down both paths, so the
    speedup is a pure path comparison.  Every cell first asserts the
    two paths produce bit-identical replicas (the engine's acceptance
    contract), then reports tracemalloc's host-allocation peak across
    one merge per path — O(frame) for the legacy densify cells,
    O(header) fused.

    CPU-backend honesty (docs/device.md "Reading the numbers"): on the
    forced-CPU backend ``np.asarray`` of a device array is zero-copy
    and XLA scatters are scalar loops, so the measured speedups are a
    conservative FLOOR — a real accelerator pays PCIe/DMA for exactly
    the crossings the fused path deletes.  The fold cells additionally
    report dispatch amortization (k frames : 1 dispatch), the
    structural win a compute-bound CPU's wall clock understates."""
    import jax
    import jax.numpy as jnp

    from dpwa_tpu import native
    from dpwa_tpu.device import MergeEngine
    from dpwa_tpu.ops import quantize as qz
    from dpwa_tpu.ops import shard as shard_ops

    try:
        import ml_dtypes
    except ImportError:  # pragma: no cover - ships with jax
        ml_dtypes = None

    alpha = 0.3
    # The pre-engine jitted lerp, verbatim: one compiled slot, alpha
    # traced, remote uploaded with a plain jnp.asarray copy.
    legacy_lerp = jax.jit(lambda x, y, t: (1.0 - t) * x + t * y)
    eng = MergeEngine()

    def timed(fn):
        fn()  # warm: compile, allocator slack, page faults
        durs = []
        for _ in range(max(1, int(iters))):
            t0 = time.perf_counter()
            fn()
            durs.append(time.perf_counter() - t0)
        return float(np.median(durs)), durs

    def alloc_peak(fn) -> int:
        tracemalloc.start()
        try:
            fn()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        return int(peak)

    frames: dict = {}
    headline = None
    spread = None
    for floats in sizes:
        d = int(floats)
        rng = np.random.default_rng(d)
        local = rng.standard_normal(d).astype(np.float32)
        remote = rng.standard_normal(d).astype(np.float32)
        dev = jnp.asarray(local)
        nbytes = d * 4

        # One decoded-frame fixture per codec family.
        int8_payload = qz.encode_int8_payload(remote, 7, 1.0, 0)
        sp = qz.decode_topk_payload(
            qz.TopkEncoder(topk_frac, "f32").encode(remote, 0, 1.0, 0)
        )
        lo, hi = shard_ops.shard_bounds(d, int(shard_k), 1)
        est_slice = np.ascontiguousarray(remote[lo:hi])

        def legacy_dense():
            np.asarray(dev)  # the old per-exchange readback
            return legacy_lerp(dev, jnp.asarray(remote), np.float32(alpha))

        def fused_dense():
            return eng.merge_dense(dev, remote, alpha)

        def legacy_int8():
            np.asarray(dev)
            dense = qz.decode_int8_payload(int8_payload)
            return legacy_lerp(dev, jnp.asarray(dense), np.float32(alpha))

        def fused_int8():
            return eng.merge_int8(dev, int8_payload, alpha)

        def legacy_topk():
            host = np.asarray(dev)
            dense = sp.densify(host)
            return legacy_lerp(dev, jnp.asarray(dense), np.float32(alpha))

        def fused_topk():
            return eng.merge_topk(dev, sp.indices, sp.values, alpha)

        def legacy_shard():
            host = np.asarray(dev)
            merged = host.copy()
            merged[lo:hi] = native.merge_out(
                np.ascontiguousarray(merged[lo:hi]), est_slice, alpha
            )
            return jnp.asarray(merged)  # the old full re-upload

        def fused_shard():
            return eng.merge_shard(dev, lo, est_slice, alpha)

        pairs = [
            ("f32", legacy_dense, fused_dense),
            ("int8", legacy_int8, fused_int8),
            ("topk", legacy_topk, fused_topk),
            ("shard", legacy_shard, fused_shard),
        ]
        if ml_dtypes is not None:
            remote_bf16 = remote.astype(ml_dtypes.bfloat16)

            def legacy_bf16():
                np.asarray(dev)
                dense = remote_bf16.astype(np.float32)  # old host upcast
                return legacy_lerp(
                    dev, jnp.asarray(dense), np.float32(alpha)
                )

            def fused_bf16():
                return eng.merge_bf16(dev, remote_bf16, alpha)

            pairs.insert(1, ("bf16", legacy_bf16, fused_bf16))

        cells: dict = {}
        for name, legacy, fused in pairs:
            if (
                np.asarray(legacy()).tobytes()
                != np.asarray(fused()).tobytes()
            ):
                raise AssertionError(
                    f"fused {name} diverged from the legacy merge "
                    f"at d={d}"
                )
            legacy_dt, _ = timed(
                lambda: legacy().block_until_ready()
            )
            fused_dt, fused_durs = timed(
                lambda: fused().block_until_ready()
            )
            cells[name] = {
                "legacy_gbps": round(nbytes / legacy_dt / 1e9, 3),
                "fused_gbps": round(nbytes / fused_dt / 1e9, 3),
                "speedup": round(legacy_dt / fused_dt, 2),
                "bit_identical": True,
                "legacy_alloc_bytes": alloc_peak(
                    lambda: legacy().block_until_ready()
                ),
                "fused_alloc_bytes": alloc_peak(
                    lambda: fused().block_until_ready()
                ),
            }
            if name == "f32":
                headline = nbytes / fused_dt / 1e9
                med = float(np.median(fused_durs))
                q1, q3 = np.percentile(fused_durs, [25, 75])
                spread = float((q3 - q1) / med) if med > 0 else None
        frames[frame_label(nbytes)] = {
            "frame_bytes": int(nbytes),
            "codecs": cells,
        }

    # Batched multi-peer folds at the smallest replica size: k legacy
    # round-trip merges vs k fused dispatches vs ONE fold dispatch.
    d0 = int(sizes[0])
    rng = np.random.default_rng(99)
    dev0 = jnp.asarray(rng.standard_normal(d0).astype(np.float32))
    fold_cells: dict = {}
    for k in fold_ks:
        k = int(k)
        remotes = [
            rng.standard_normal(d0).astype(np.float32) for _ in range(k)
        ]
        alphas = [alpha] * k

        def legacy_seq():
            x = dev0
            for r in remotes:
                np.asarray(x)  # per-merge readback, the old cadence
                x = legacy_lerp(x, jnp.asarray(r), np.float32(alpha))
            return x

        def fused_seq():
            x = dev0
            for r in remotes:
                x = eng.merge_dense(x, r, alpha)
            return x

        def fold_once():
            return eng.fold(dev0, remotes, alphas)

        if (
            np.asarray(fused_seq()).tobytes()
            != np.asarray(fold_once()).tobytes()
        ):
            raise AssertionError(
                f"k={k} fold diverged from sequential merges"
            )
        legacy_dt, _ = timed(lambda: legacy_seq().block_until_ready())
        seq_dt, _ = timed(lambda: fused_seq().block_until_ready())
        fold_dt, _ = timed(lambda: fold_once().block_until_ready())
        fold_cells[f"k{k}"] = {
            "frames": k,
            "legacy_sequential_gbps": round(
                k * d0 * 4 / legacy_dt / 1e9, 3
            ),
            "fused_sequential_gbps": round(k * d0 * 4 / seq_dt / 1e9, 3),
            "fold_gbps": round(k * d0 * 4 / fold_dt / 1e9, 3),
            "speedup_vs_legacy": round(legacy_dt / fold_dt, 2),
            "dispatch_amortization": k,
            "bit_identical": True,
        }

    best = max(
        cell["speedup"]
        for fr in frames.values()
        for cell in fr["codecs"].values()
    )
    return {
        "iters": int(iters),
        "sizes_floats": [int(s) for s in sizes],
        "alpha": alpha,
        "topk_frac": float(topk_frac),
        "shard_k": int(shard_k),
        "frames": frames,
        "fold_frame_floats": d0,
        "fold": fold_cells,
        "best_speedup": best,
        "merge_fused_gbps": (
            round(headline, 3) if headline is not None else None
        ),
        "spread_iqr_frac": (
            round(spread, 4) if spread is not None else None
        ),
        "backend": jax.default_backend(),
        "engine": eng.snapshot(),
    }


# ---------------------------------------------------------------------------
# Watchdog'd subprocess orchestration (main process never imports JAX, so
# the one device child has the chip to itself).
# ---------------------------------------------------------------------------

def run_leg(
    leg: str, extra: list[str], tag: str, timeout_s: float, env: dict,
    json_tag: str | None = None,
):
    """Run one benchmark leg as a watchdog'd subprocess; GB/s or None.

    With ``json_tag`` set, also parses that tag's JSON payload line and
    returns ``(gbps, payload_dict | None)`` instead of the bare float —
    the TCP leg ships its spread statistics alongside the headline."""
    cmd = [sys.executable, os.path.abspath(__file__), leg, *extra]
    val = payload = None
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s, env=env
        )
    except subprocess.TimeoutExpired:
        log(f"{leg} HUNG past {timeout_s:.0f}s — killed")
        return (None, None) if json_tag else None
    sys.stderr.write(proc.stderr or "")
    if proc.returncode != 0:
        log(f"{leg} failed rc={proc.returncode}")
        return (None, None) if json_tag else None
    for line in proc.stdout.splitlines():
        if line.startswith(tag + " "):
            val = float(line.split()[1])
        elif json_tag and line.startswith(json_tag + " "):
            try:
                payload = json.loads(line.split(None, 1)[1])
            except json.JSONDecodeError:
                log(f"{leg} produced an unparseable {json_tag} line")
    if val is None:
        log(f"{leg} produced no {tag} line")
    return (val, payload) if json_tag else val


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--size", type=int, default=24 * 1024 * 1024,
        help="flat vector length (floats); default ~100MB, ResNet-50 scale "
        "(multiple of 1024 so the Pallas fast path applies)",
    )
    ap.add_argument("--peers", type=int, default=8)
    ap.add_argument(
        "--iters", type=int, default=200,
        help="device-leg exchange iterations; high enough that the one "
        "closing block_until_ready is noise next to device time",
    )
    ap.add_argument("--tcp-iters", type=int, default=5)
    ap.add_argument(
        "--tcp-repeats", type=int, default=3,
        help="independent TCP-leg measurement passes; the reported "
        "baseline is the median of the per-pass medians",
    )
    ap.add_argument(
        "--tcp-warmups", type=int, default=3,
        help="throwaway TCP exchanges before the measured passes "
        "(sockets, allocator pools, and the receive ring start cold)",
    )
    ap.add_argument(
        "--tcp-size", type=int, default=0,
        help="TCP vector length (defaults to --size)",
    )
    ap.add_argument(
        "--device-timeout", type=float, default=600.0,
        help="seconds before the device benchmark leg is declared hung",
    )
    ap.add_argument(
        "--device-leg", action="store_true",
        help="(internal) run only the device benchmark in this process",
    )
    ap.add_argument(
        "--tcp-leg", action="store_true",
        help="(internal) run only the TCP baseline in this process",
    )
    ap.add_argument(
        "--wire-size", type=int, default=4 * 1024 * 1024,
        help="vector length for the wire-codec sweep (floats)",
    )
    ap.add_argument(
        "--wire-iters", type=int, default=8,
        help="exchange rounds per codec in the wire sweep",
    )
    ap.add_argument(
        "--wire-leg", action="store_true",
        help="(internal) run only the wire-codec sweep in this process",
    )
    ap.add_argument(
        "--skip-wire", action="store_true",
        help="skip the wire-codec sweep leg",
    )
    ap.add_argument(
        "--serve-frame-floats", type=int, default=16 * 1024,
        help="blob length (floats) served in the Rx serve leg (~64KB)",
    )
    ap.add_argument(
        "--serve-seconds", type=float, default=1.2,
        help="duration of each server's frames/sec sub-leg",
    )
    ap.add_argument(
        "--serve-leg", action="store_true",
        help="(internal) run only the Rx serve leg in this process",
    )
    ap.add_argument(
        "--skip-serve", action="store_true",
        help="skip the Rx serve leg (threaded vs reactor)",
    )
    ap.add_argument(
        "--hier-leg", action="store_true",
        help="run ONLY the hierarchical-gossip sweep: island_size x "
        "island_count at fixed --hier-peers, wide-area frame multiplier "
        "vs the flat ring + convergence rounds, gated against "
        "bench_history.jsonl medians",
    )
    ap.add_argument(
        "--hier-peers", type=int, default=64,
        help="total peers for the hier sweep (islands partition this)",
    )
    ap.add_argument(
        "--hier-rounds", type=int, default=64,
        help="gossip rounds per hier sweep point",
    )
    ap.add_argument(
        "--hier-target", type=float, default=0.05,
        help="rel_rms convergence target for rounds_to_target",
    )
    ap.add_argument(
        "--hier-island-sizes", type=str, default="4,8,16",
        help="comma-separated island sizes to sweep (sizes that do not "
        "divide --hier-peers are skipped)",
    )
    ap.add_argument(
        "--shard-leg", action="store_true",
        help="run ONLY the sharded-wire sweep: bytes/frame at shard.k in "
        "--shard-ks for the dense f32 wire and composed with the top-k "
        "codec, reductions measured within each codec family vs its k=1 "
        "leg; appends its own bench_history.jsonl record",
    )
    ap.add_argument(
        "--shard-size", type=int, default=1024 * 1024,
        help="vector length for the shard sweep (floats)",
    )
    ap.add_argument(
        "--shard-iters", type=int, default=8,
        help="exchange rounds per shard-sweep leg (>= max k, so every "
        "leg reaches full round-robin coverage)",
    )
    ap.add_argument(
        "--shard-ks", type=str, default="1,2,4,8",
        help="comma-separated shard counts to sweep (1 = the unsharded "
        "baseline the reductions are measured against)",
    )
    ap.add_argument(
        "--copy-leg", action="store_true",
        help="run ONLY the zero-copy frame-path leg: old chunk-grow "
        "fetch loop vs the recv_into receive ring, per Rx server and "
        "frame size — frames/sec, GB/s, speedup, and tracemalloc's "
        "per-frame decode allocation; appends its own "
        "bench_history.jsonl record",
    )
    ap.add_argument(
        "--copy-frame-floats", type=str,
        default=",".join(str(s) for s in COPY_SWEEP_FRAME_FLOATS),
        help="comma-separated frame sizes (floats) for the copy leg",
    )
    ap.add_argument(
        "--copy-iters", type=int, default=5,
        help="timed fetches per (server, size, path) copy-leg cell",
    )
    ap.add_argument(
        "--merge-leg", action="store_true",
        help="run ONLY the device merge-engine leg: the pre-engine "
        "readback+densify+upload merge vs the fused decode+lerp "
        "kernels, per codec family and replica size, plus batched "
        "multi-peer folds — GB/s, speedup, bit-identity, per-merge "
        "host allocation; appends its own bench_history.jsonl record "
        "carrying a merge_gate verdict",
    )
    ap.add_argument(
        "--merge-leg-run", action="store_true",
        help="internal: the merge leg's backend-pinned subprocess "
        "entry (use --merge-leg)",
    )
    ap.add_argument(
        "--merge-frame-floats", type=str,
        default=",".join(str(s) for s in MERGE_SWEEP_FRAME_FLOATS),
        help="comma-separated replica sizes (floats) for the merge leg",
    )
    ap.add_argument(
        "--merge-iters", type=int, default=5,
        help="timed merges per (codec, size, path) merge-leg cell",
    )
    ap.add_argument(
        "--merge-fold-ks", type=str, default="2,4,8",
        help="comma-separated fold widths (frames per batched "
        "dispatch) for the merge leg's multi-peer fold cells",
    )
    ap.add_argument(
        "--async-leg", action="store_true",
        help="run ONLY the async gossip leg: lock-step vs barrier-free "
        "rounds at 4 peers with one chaos-shaped trickling straggler — "
        "honest peers' p50/p99 round walls and the straggler-"
        "unthrottled speedup; appends its own bench_history.jsonl "
        "record carrying an async_gate verdict",
    )
    ap.add_argument(
        "--async-size", type=int, default=ASYNC_SWEEP_FLOATS,
        help="replica size (floats) for the async leg",
    )
    ap.add_argument(
        "--async-iters", type=int, default=24,
        help="rounds per async-leg drive",
    )
    ap.add_argument(
        "--async-peers", type=int, default=ASYNC_SWEEP_PEERS,
        help="peer count for the async leg (last peer is the straggler)",
    )
    ap.add_argument(
        "--async-trickle-bytes", type=float, default=2048.0,
        help="straggler serving rate (bytes/s) for the async leg",
    )
    ap.add_argument(
        "--tune-leg", action="store_true",
        help="run ONLY the self-tuning-wire leg: static f32 vs static "
        "int8 vs the per-link controller over a congested-fabric "
        "4-peer fleet (two trickled peers, two bandwidth-flapping "
        "with full-speed clear blocks) — settled-regime round walls, "
        "merge counts, and the fidelity-shed unthrottle ratio; "
        "appends its own bench_history.jsonl record carrying a "
        "tune_gate verdict",
    )
    ap.add_argument(
        "--tune-size", type=int, default=4096,
        help="replica size (floats) for the tune leg",
    )
    ap.add_argument(
        "--tune-iters", type=int, default=48,
        help="rounds per tune-leg drive (the ladder walk needs the "
        "first two-thirds; walls settle over the last third)",
    )
    ap.add_argument(
        "--tune-trickle-bytes", type=float, default=8192.0,
        help="trickled peers' serving rate (bytes/s) for the tune leg",
    )
    ap.add_argument(
        "--fleet-leg", action="store_true",
        help="run ONLY the fleet partial-view leg: orchestrator soaks "
        "at --fleet-peers under a fixed membership.view block, "
        "recording per-node resident control-plane bytes and digest "
        "bytes/frame (the O(sample)/O(state_cap) acceptance); appends "
        "its own bench_history.jsonl record carrying a fleet_gate "
        "verdict",
    )
    ap.add_argument(
        "--fleet-peers", type=str, default="256,1024,4096",
        help="comma-separated fleet sizes for the fleet leg",
    )
    ap.add_argument(
        "--fleet-rounds", type=int, default=24,
        help="churn rounds per fleet-leg soak",
    )
    ap.add_argument(
        "--train-leg", action="store_true",
        help="run ONLY the end-to-end training leg: the clean chaos-"
        "certification leg (dpwa_tpu/run/) — gossip SGD at --train-"
        "peers vs a single-process control arm at equal total steps — "
        "recorded with a train_gate verdict on steps-to-target-loss; "
        "appends its own bench_history.jsonl record",
    )
    ap.add_argument(
        "--train-leg-run", action="store_true",
        help="internal: the train leg's backend-pinned subprocess "
        "entry (use --train-leg)",
    )
    ap.add_argument(
        "--train-task", type=str, default="blobs",
        help="training task for the train leg (dpwa_tpu/run/task.py "
        "registry: blobs, digits, lora)",
    )
    ap.add_argument(
        "--train-peers", type=int, default=8,
        help="peer count for the train leg",
    )
    ap.add_argument(
        "--train-base-port", type=int, default=47400,
        help="base TCP port for the train leg's gossip cohort",
    )
    ap.add_argument(
        "--train-timeout", type=float, default=600.0,
        help="watchdog timeout (s) for the train leg subprocess",
    )
    args = ap.parse_args()

    if args.device_leg:
        gbps, info = bench_device(args.size, args.peers, args.iters)
        print(f"DEVICE_GBPS {gbps:.6f}", flush=True)
        print("DEVICE_INFO " + json.dumps(info), flush=True)
        return
    if args.tcp_leg:
        pinned = pin_cpu_budget(TCP_LEG_CPU_BUDGET)
        if not pinned:
            log("tcp leg: CPU pinning unavailable; baseline is unpinned")
        stats = bench_tcp(
            args.tcp_size or args.size, args.tcp_iters,
            repeats=args.tcp_repeats, warmups=args.tcp_warmups,
        )
        print(f"TCP_GBPS {stats['gbps']:.6f}", flush=True)
        print("TCP_STATS " + json.dumps(stats), flush=True)
        return
    if args.wire_leg:
        sweep = bench_wire(args.wire_size, args.wire_iters)
        print("WIRE_SWEEP " + json.dumps(sweep), flush=True)
        return
    if args.merge_leg_run:
        sizes = [
            int(s) for s in args.merge_frame_floats.split(",") if s.strip()
        ]
        ks = [int(s) for s in args.merge_fold_ks.split(",") if s.strip()]
        sweep = bench_merge(sizes, args.merge_iters, ks)
        print("MERGE_SWEEP " + json.dumps(sweep), flush=True)
        if sweep.get("merge_fused_gbps") is not None:
            print(
                f"MERGE_GBPS {sweep['merge_fused_gbps']:.6f}", flush=True
            )
        return
    if args.serve_leg:
        res = bench_serve(args.serve_frame_floats, args.serve_seconds)
        print("SERVE_LEG " + json.dumps(res), flush=True)
        return
    if args.train_leg_run:
        # In-process arm of --train-leg (imports jax; the parent pins
        # the backend and scrubs the env before spawning this).
        import tempfile

        from dpwa_tpu.run.legs import clean_leg

        workdir = tempfile.mkdtemp(prefix="dpwa-train-leg-")
        res = clean_leg(
            workdir,
            n_peers=args.train_peers,
            task=args.train_task,
            base_port=args.train_base_port,
        )
        payload = res.to_record()
        print("TRAIN_LEG " + json.dumps(payload), flush=True)
        stt = payload["verdict"].get("gossip_steps_to_target")
        if stt is not None:
            print(f"TRAIN_STEPS {float(stt):.6f}", flush=True)
        return
    if args.hier_leg:
        # Standalone mode (like the other legs, but user-facing): the
        # engine is numpy + threefry draws, so it runs in-process on the
        # CPU backend.  Appends its own record="bench" history line so
        # the hier gate has medians to judge future runs against.
        sizes = [
            int(s) for s in args.hier_island_sizes.split(",") if s.strip()
        ]
        log(
            f"hier sweep: {args.hier_peers} peers, sizes {sizes}, "
            f"{args.hier_rounds} rounds, target {args.hier_target} ..."
        )
        hier = bench_hier(
            args.hier_peers, sizes, args.hier_rounds, args.hier_target
        )
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        hier["hier_gate"] = hier_gate(
            read_bench_history(history_path), hier["wide_multiplier_min"]
        )
        if hier["hier_gate"]["verdict"] not in ("ok", "no_data"):
            log(
                f"hier gate: multiplier {hier['hier_gate']['verdict']} "
                f"(current {hier['hier_gate']['current_mult']} vs median "
                f"{hier['hier_gate']['median_mult']})"
            )
        out = {
            "metric": "hier_wide_frame_multiplier",
            "bench_methodology": BENCH_METHODOLOGY,
            "hier": hier,
        }
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.shard_leg:
        # Standalone mode (the --hier-leg pattern): transports on the
        # CPU backend, in-process.  Appends its own record="bench"
        # history line stamped with the current methodology.
        ks = [int(s) for s in args.shard_ks.split(",") if s.strip()]
        if 1 not in ks:
            ks = [1] + ks  # reductions are measured against the k=1 leg
        log(
            f"shard sweep: d={args.shard_size}, ks {ks}, "
            f"x{args.shard_iters} rounds ..."
        )
        sweep = bench_shard(args.shard_size, args.shard_iters, ks=ks)
        floor = sweep.get("reduction_floor_frac")
        for fam in ("f32", "topk"):
            worst = max(k for k in ks)
            leg = sweep["legs"].get(f"{fam}_k{worst}")
            if leg is not None:
                log(
                    f"shard sweep: {fam} k={worst} -> "
                    f"{leg['wire_bytes_per_frame']} B/frame, "
                    f"{leg['reduction_vs_k1']}x vs k=1"
                )
        log(
            f"shard sweep: min(reduction_vs_k1 / k) over k>1 = {floor} "
            "(acceptance >= 0.9)"
        )
        out = {
            "metric": "shard_wire_byte_reduction",
            "bench_methodology": BENCH_METHODOLOGY,
            "shard_sweep": sweep,
        }
        print("SHARD_SWEEP " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.async_leg:
        # Standalone mode (the --shard-leg pattern): transports
        # in-process on the CPU backend.  Appends its own record="bench"
        # history line carrying the async_gate verdict.
        log(
            f"async leg: {args.async_peers} peers, d={args.async_size}, "
            f"x{args.async_iters} rounds, straggler trickle "
            f"{args.async_trickle_bytes:.0f} B/s ..."
        )
        sweep = bench_async(
            args.async_size, args.async_iters, peers=args.async_peers,
            trickle_bytes_per_s=args.async_trickle_bytes,
        )
        log(
            f"async leg: honest p99 {sweep['lockstep']['p99_ms']} ms "
            f"lock-step -> {sweep['async']['p99_ms']} ms async "
            f"({sweep['straggler_speedup']}x unthrottled), async "
            f"merges {sweep['async'].get('async_merges')}, stale drops "
            f"{sweep['async'].get('async_stale_drops')}"
        )
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        gate = async_gate(
            read_bench_history(history_path), sweep["straggler_speedup"]
        )
        log(f"async leg: gate {gate['verdict']}")
        out = {
            "metric": "async_straggler_unthrottle",
            "bench_methodology": BENCH_METHODOLOGY,
            "async_leg": sweep,
            "async_straggler_speedup": sweep["straggler_speedup"],
            "async_gate": gate,
        }
        print("ASYNC_LEG " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.tune_leg:
        # Standalone mode (the --async-leg pattern): transports
        # in-process on the CPU backend.  Appends its own record="bench"
        # history line carrying the tune_gate verdict.
        log(
            f"tune leg: 4 peers (flapping/trickled/flapping/trickled), "
            f"d={args.tune_size}, x{args.tune_iters} rounds, trickle "
            f"{args.tune_trickle_bytes:.0f} B/s ..."
        )
        sweep = bench_tune(
            args.tune_size, args.tune_iters,
            trickle_bytes_per_s=args.tune_trickle_bytes,
        )
        log(
            f"tune leg: settled p50 "
            f"{sweep['static_f32']['settled_p50_ms']} ms static f32 -> "
            f"{sweep['tuned']['settled_p50_ms']} ms tuned "
            f"({sweep['tune_unthrottle']}x unthrottled, "
            f"{sweep['tune_vs_best_static']}x vs int8), merges "
            f"{sweep['static_f32']['merges']} -> "
            f"{sweep['tuned']['merges']}, escalations "
            f"{sweep['tuned'].get('escalations')}, dwell violations "
            f"{sweep['tuned'].get('dwell_violations')}"
        )
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        gate = tune_gate(
            read_bench_history(history_path), sweep["tune_unthrottle"]
        )
        log(f"tune leg: gate {gate['verdict']}")
        out = {
            "metric": "tune_fidelity_shed_unthrottle",
            "bench_methodology": BENCH_METHODOLOGY,
            "tune_leg": sweep,
            "tune_unthrottle": sweep["tune_unthrottle"],
            "tune_gate": gate,
        }
        print("TUNE_LEG " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.fleet_leg:
        # Standalone mode (the --async-leg pattern): the plane-level
        # orchestrator in-process on the CPU backend.  Appends its own
        # record="bench" history line carrying the fleet_gate verdict.
        ns = [int(s) for s in args.fleet_peers.split(",") if s.strip()]
        log(
            f"fleet leg: peers {ns}, {args.fleet_rounds} churn rounds, "
            f"view {FLEET_LEG_VIEW['digest_sample']}-sample / "
            f"{FLEET_LEG_VIEW['state_cap']}-cap ..."
        )
        sweep = bench_fleet(ns, rounds=args.fleet_rounds)
        for name in sorted(sweep["legs"]):
            leg = sweep["legs"][name]
            log(
                f"fleet leg: {name} -> resident "
                f"{leg['resident_bytes_max']} B/node (max), digest "
                f"{leg['digest_bytes_max']} B/frame, tracked "
                f"{leg['tracked_max']}, {leg['round_wall_ms']} ms/round"
            )
        log(
            f"fleet leg: {sweep['peer_scaling']}x peers -> "
            f"{sweep['resident_scaling']}x resident bytes, "
            f"{sweep['digest_scaling']}x digest bytes"
        )
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        gate = fleet_gate(
            read_bench_history(history_path),
            sweep["fleet_resident_bytes"],
        )
        log(f"fleet leg: gate {gate['verdict']}")
        out = {
            "metric": "fleet_bounded_view_residency",
            "bench_methodology": BENCH_METHODOLOGY,
            "fleet_leg": sweep,
            "fleet_resident_bytes": sweep["fleet_resident_bytes"],
            "fleet_digest_bytes": sweep["fleet_digest_bytes"],
            "fleet_gate": gate,
        }
        print("FLEET_LEG " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.copy_leg:
        # Standalone mode (the --shard-leg pattern): raw servers +
        # fetchers in-process on the CPU backend.  Appends its own
        # record="bench" history line stamped with the methodology.
        sizes = [
            int(s) for s in args.copy_frame_floats.split(",") if s.strip()
        ]
        log(
            f"copy leg: frames {[frame_label(s * 4) for s in sizes]}, "
            f"x{args.copy_iters} fetches per cell ..."
        )
        sweep = bench_copy(sizes, args.copy_iters)
        for fr_name, fr in sweep["frames"].items():
            for srv_name, leg in fr["servers"].items():
                log(
                    f"copy leg: {fr_name} [{srv_name}] "
                    f"{leg['legacy_fps']} -> {leg['zerocopy_fps']} "
                    f"frames/s ({leg['speedup']}x, "
                    f"{leg['zerocopy_gbps']} GB/s), decode alloc "
                    f"{leg['decode_alloc_per_frame_bytes']} B/frame"
                )
        log(f"copy leg: best speedup {sweep['best_speedup']}x")
        log(
            "copy leg: small-frame decode alloc "
            f"{'OK' if sweep['small_frame_alloc_ok'] else 'EXCEEDED'} "
            f"(cap {sweep['alloc_cap_bytes']} B)"
        )
        out = {
            "metric": "zero_copy_frame_path",
            "bench_methodology": BENCH_METHODOLOGY,
            "copy": sweep,
        }
        print("COPY_LEG " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.merge_leg:
        # The leg imports jax, so it runs as a backend-pinned watchdog'd
        # subprocess (the TCP-baseline pattern) — the main process never
        # imports JAX.
        mib = [
            int(s) * 4 // (1 << 20)
            for s in args.merge_frame_floats.split(",") if s.strip()
        ]
        log(
            f"merge leg: replicas {mib} MiB, x{args.merge_iters} merges "
            "per cell ..."
        )
        cpu_env = os.environ.copy()
        cpu_env["JAX_PLATFORMS"] = "cpu"
        gbps, sweep = run_leg(
            "--merge-leg-run",
            [
                "--merge-frame-floats", args.merge_frame_floats,
                "--merge-iters", str(args.merge_iters),
                "--merge-fold-ks", args.merge_fold_ks,
            ],
            "MERGE_GBPS", args.device_timeout, cpu_env,
            json_tag="MERGE_SWEEP",
        )
        if sweep:
            for fr_name, fr in sweep["frames"].items():
                for codec, cell in fr["codecs"].items():
                    log(
                        f"merge leg: {fr_name} [{codec}] "
                        f"{cell['legacy_gbps']} -> {cell['fused_gbps']} "
                        f"GB/s ({cell['speedup']}x), fused alloc "
                        f"{cell['fused_alloc_bytes']} B/merge"
                    )
            for kname, cell in sweep["fold"].items():
                log(
                    f"merge leg: fold {kname} "
                    f"{cell['legacy_sequential_gbps']} -> "
                    f"{cell['fold_gbps']} GB/s "
                    f"({cell['speedup_vs_legacy']}x, "
                    f"{cell['dispatch_amortization']} frames/dispatch)"
                )
            log(f"merge leg: best speedup {sweep['best_speedup']}x")
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        gate = merge_gate(
            read_bench_history(history_path), gbps,
            spread_iqr_frac=(sweep or {}).get("spread_iqr_frac"),
        )
        log(f"merge leg: gate {gate['verdict']}")
        out = {
            "metric": "device_merge_engine",
            "bench_methodology": BENCH_METHODOLOGY,
            "merge": sweep,
            "merge_fused_gbps": gbps,
            "merge_gate": gate,
        }
        print("MERGE_LEG " + json.dumps(sweep), flush=True)
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return
    if args.train_leg:
        # The leg imports jax (real optimizer steps through the real
        # gossip stack), so it runs as a backend-pinned watchdog'd
        # subprocess (the merge-leg pattern) and the parent judges the
        # result: the clean leg's own chaos-certification verdict plus
        # a time-to-quality band against recent history.
        log(
            f"train leg: {args.train_peers} peers, task "
            f"{args.train_task}, vs single-process control arm ..."
        )
        cpu_env = os.environ.copy()
        cpu_env["JAX_PLATFORMS"] = "cpu"
        stt, leg = run_leg(
            "--train-leg-run",
            [
                "--train-task", args.train_task,
                "--train-peers", str(args.train_peers),
                "--train-base-port", str(args.train_base_port),
            ],
            "TRAIN_STEPS", args.train_timeout, cpu_env,
            json_tag="TRAIN_LEG",
        )
        verdict = (leg or {}).get("verdict", {})
        if leg:
            log(
                f"train leg: gossip steps-to-target "
                f"{verdict.get('gossip_steps_to_target')} vs single "
                f"{verdict.get('single_steps_to_target')} "
                f"(tol {verdict.get('steps_tol')}x), leg "
                f"{'ok' if leg.get('ok') else 'FAILED'}"
            )
        history_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "artifacts", "bench_history.jsonl",
        )
        gate = train_gate(
            read_bench_history(history_path), stt,
            bool(leg and leg.get("ok")),
        )
        log(f"train leg: gate {gate['verdict']}")
        out = {
            "metric": "train_time_to_quality",
            "bench_methodology": BENCH_METHODOLOGY,
            "train": leg,
            "train_steps_to_target": stt,
            "train_gate": gate,
        }
        print("TRAIN_LEG " + json.dumps(leg), flush=True)
        print(json.dumps(out), flush=True)
        try:
            os.makedirs(os.path.dirname(history_path), exist_ok=True)
            with open(history_path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps({"record": "bench", "t": time.time(), **out})
                    + "\n"
                )
        except OSError:
            pass
        return

    # --- TCP baseline.  Subprocess pinned to the CPU backend: the transport
    # itself is pure stdlib, but its schedule/interpolation imports touch
    # jax, and a chip belongs to one process — the device child below.
    tcp_d = args.tcp_size or args.size
    log(f"TCP baseline: d={tcp_d} ({tcp_d * 4 / 1e6:.0f} MB) ...")
    cpu_env = os.environ.copy()
    cpu_env["JAX_PLATFORMS"] = "cpu"
    tcp_gbps, tcp_stats = run_leg(
        "--tcp-leg",
        [
            "--tcp-size", str(tcp_d),
            "--tcp-iters", str(args.tcp_iters),
            "--tcp-repeats", str(args.tcp_repeats),
            "--tcp-warmups", str(args.tcp_warmups),
        ],
        "TCP_GBPS", args.device_timeout, cpu_env, json_tag="TCP_STATS",
    )
    if tcp_gbps is not None:
        spread = (tcp_stats or {}).get("spread_iqr_frac")
        log(
            f"TCP baseline: {tcp_gbps:.3f} GB/s/peer"
            + (f" (pass spread {spread:.1%})" if spread is not None else "")
        )

    # --- Wire-codec sweep: bytes/frame + compression ratio per codec and a
    # prefetch-overlap leg, in the same CPU-pinned subprocess pattern as
    # the TCP baseline (the transport imports touch jax).
    wire_sweep = None
    if not args.skip_wire:
        log(f"wire sweep: d={args.wire_size} x{args.wire_iters} ...")
        wire_cmd = [
            sys.executable, os.path.abspath(__file__), "--wire-leg",
            "--wire-size", str(args.wire_size),
            "--wire-iters", str(args.wire_iters),
        ]
        try:
            proc = subprocess.run(
                wire_cmd, capture_output=True, text=True,
                timeout=args.device_timeout, env=cpu_env,
            )
            sys.stderr.write(proc.stderr or "")
            if proc.returncode != 0:
                log(f"wire leg failed rc={proc.returncode}")
            else:
                for line in proc.stdout.splitlines():
                    if line.startswith("WIRE_SWEEP "):
                        wire_sweep = json.loads(line.split(None, 1)[1])
        except subprocess.TimeoutExpired:
            log(f"wire leg HUNG past {args.device_timeout:.0f}s — killed")
        except json.JSONDecodeError:
            log("wire leg produced an unparseable WIRE_SWEEP line")
        if wire_sweep is not None:
            tk = wire_sweep["legs"].get("topk_0.05", {})
            ov = wire_sweep.get("overlap", {})
            log(
                "wire sweep: topk@0.05 "
                f"{tk.get('reduction_vs_f32')}x vs f32, "
                f"{tk.get('reduction_vs_int8')}x vs int8; overlap "
                f"hidden_frac={ov.get('hidden_frac')}"
            )
            spans = wire_sweep.get("spans") or {}
            if spans:
                log(
                    "obs leg: overhead "
                    f"{spans.get('obs_overhead_pct')}% over f32; stage "
                    f"medians {spans.get('stage_median_ms')}"
                )

    # --- Rx serve leg (ISSUE 10): threaded vs reactor frames/sec +
    # held-connection capacity sweep, in the same CPU-pinned subprocess
    # pattern (the server modules import numpy/flowctl only, but the
    # transport package __init__ touches jax).
    serve = None
    if not args.skip_serve:
        log(
            f"serve leg: frame={args.serve_frame_floats * 4 / 1024:.0f}KB "
            f"x{args.serve_seconds:.1f}s, sweep {list(SERVE_SWEEP)} ..."
        )
        serve_cmd = [
            sys.executable, os.path.abspath(__file__), "--serve-leg",
            "--serve-frame-floats", str(args.serve_frame_floats),
            "--serve-seconds", str(args.serve_seconds),
        ]
        try:
            proc = subprocess.run(
                serve_cmd, capture_output=True, text=True,
                timeout=args.device_timeout, env=cpu_env,
            )
            sys.stderr.write(proc.stderr or "")
            if proc.returncode != 0:
                log(f"serve leg failed rc={proc.returncode}")
            else:
                for line in proc.stdout.splitlines():
                    if line.startswith("SERVE_LEG "):
                        serve = json.loads(line.split(None, 1)[1])
        except subprocess.TimeoutExpired:
            log(f"serve leg HUNG past {args.device_timeout:.0f}s — killed")
        except json.JSONDecodeError:
            log("serve leg produced an unparseable SERVE_LEG line")
        if serve is not None:
            sv = serve.get("servers", {})
            thr = sv.get("threaded", {})
            rx = sv.get("reactor", {})
            log(
                "serve leg: reactor "
                f"{rx.get('frames_per_s')} f/s vs threaded "
                f"{thr.get('frames_per_s')} f/s; capacity "
                f"{rx.get('capacity_conns')} vs "
                f"{thr.get('capacity_conns')} held conns "
                f"({serve.get('capacity_ratio')}x)"
            )

    # --- The device leg: one child, on whatever platform JAX selects.  No
    # CPU fallback and no replay — a run that did not reach an accelerator
    # has no device number, and says so with its exit code.
    log(f"device leg: d={args.size} peers={args.peers} x{args.iters} ...")
    dev_gbps, dev_info = run_leg(
        "--device-leg",
        [
            "--size", str(args.size),
            "--peers", str(args.peers),
            "--iters", str(args.iters),
        ],
        "DEVICE_GBPS", args.device_timeout, os.environ.copy(),
        json_tag="DEVICE_INFO",
    )
    if dev_gbps is None or dev_info is None:
        log("device leg failed — no result")
        sys.exit(1)
    if dev_info["platform"] == "cpu":
        log(
            f"device leg landed on the CPU ({dev_gbps:.2f} GB/s there is "
            "not a device number) — no result"
        )
        sys.exit(1)
    log(
        f"device path [{dev_info['platform']} {dev_info['device_kind']} "
        f"x{dev_info['device_count']}, {dev_info['path']}]: "
        f"{dev_gbps:.2f} GB/s/chip"
    )

    out = {
        "metric": "pairwise_avg_bandwidth",
        "bench_methodology": BENCH_METHODOLOGY,
        "value": round(dev_gbps, 3),
        "unit": "GB/s/chip",
        "vs_baseline": (
            round(dev_gbps / tcp_gbps, 2) if tcp_gbps else None
        ),
        "platform": dev_info["platform"],
        "device_kind": dev_info["device_kind"],
        "device_count": dev_info["device_count"],
        "device_path": dev_info["path"],
        "tcp_baseline_gbps": (
            round(tcp_gbps, 3) if tcp_gbps is not None else None
        ),
        # Pass dispersion of the baseline measurement itself (IQR of
        # per-pass GB/s over their median): the gate below refuses a
        # verdict when this wobbles past its tolerance.
        "tcp_baseline_spread": (tcp_stats or {}).get("spread_iqr_frac"),
    }
    if wire_sweep is not None:
        out["wire_sweep"] = wire_sweep
    if serve is not None:
        out["serve"] = serve

    # TCP-baseline regression gate (against runs BEFORE this one): a
    # drifting denominator silently inflates vs_baseline, so every run
    # records where today's baseline sits against the recent medians.
    history_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "artifacts", "bench_history.jsonl",
    )
    out["tcp_gate"] = tcp_gate(
        read_bench_history(history_path), tcp_gbps,
        spread_iqr_frac=(tcp_stats or {}).get("spread_iqr_frac"),
    )
    if out["tcp_gate"]["verdict"] not in ("ok", "no_data"):
        log(
            f"tcp gate: baseline {out['tcp_gate']['verdict']} "
            f"(current {out['tcp_gate']['current_gbps']} vs median "
            f"{out['tcp_gate']['median_gbps']} GB/s) — vs_baseline is "
            "suspect this run"
        )

    print(json.dumps(out), flush=True)

    # Cumulative history: one line per run so the perf trajectory is
    # machine-readable across PRs (schema: record="bench" envelope,
    # payload = this run's parsed result, tools/schema_check.py).
    try:
        os.makedirs(os.path.dirname(history_path), exist_ok=True)
        with open(history_path, "a", encoding="utf-8") as f:
            f.write(
                json.dumps({"record": "bench", "t": time.time(), **out})
                + "\n"
            )
    except OSError:
        pass  # history is best-effort; the stdout record is the output


if __name__ == "__main__":
    main()
