"""Barrier-free async gossip rounds (docs/async.md).

:class:`AsyncExchangeEngine` decouples **publish** from **merge**: the
lock-step round loop (publish → fetch → guard → trust → merge, one
partner per round, the round gated on that partner's stream) becomes a
free-running loop in which partner frames stream on background slots,
land in a bounded per-peer pending queue, and merge **whenever ready**.
A trickling straggler's fetch simply stays in flight across rounds while
every healthy peer keeps exchanging at full rate — the round wall never
tracks the slowest peer.

The price of barrier-freedom is staleness, and the engine makes it a
first-class, bounded quantity:

- **Staleness damping** — a frame whose publish clock lags the local
  clock by ``L`` merges at ``alpha * staleness_damping**L``, composing
  multiplicatively with the trust damping already applied through
  ``interpolation._clamped`` (the trust scale rides the transport's
  ``_pending_trust_scale`` hook; the staleness factor scales the final
  alpha — same channel, one multiplication).
- **Bounded-staleness drop** — ``lag > max_staleness`` drops the frame
  as the soft ``stale`` outcome (weight like ``slow``): lag is load
  evidence, so it degrades the peer in the scoreboard but can never
  quarantine it.
- **Deduplication** — the transport-level publish-clock guard
  (``TcpTransport._async_guard``, armed by this engine) rejects a
  publish clock that already merged, so a frame delivered both through
  a prefetch slot and the async queue can never merge twice.

Determinism contract (dpwalint enforces the ``det-*`` rules on this
module): every scheduling decision — queue admission, the drop rule,
drain order, fold grouping — is a pure function of publish clocks and
the registered ``async_drain_draw`` threefry stream (tag 33).  Wall
time feeds telemetry spans ONLY, and always through the injected
``now`` callable (the flowctl ``vclock`` seam), so a soak driven under a
:class:`~dpwa_tpu.flowctl.vclock.VirtualClock` with a scripted arrival
plan is bit-identical across reruns, spans included.

Composition with the existing planes:

- dense frames pending together fold through the device merge engine's
  batched ``fold`` dispatch (one kernel for the run — bit-identical to
  sequential merges, the ``lax.scan`` contract);
- shard frames merge only their ``[lo, hi)`` slice (the transport's
  ``_pending_shard`` double-buffer), bit-exact per slice;
- every frame still runs the full consume leg — decode, zero-energy
  guard, trust screen, scoreboard, estimator — charged to the consuming
  round's step, exactly like the prefetch pipeline.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from dpwa_tpu.flowctl.vclock import monotonic_now
from dpwa_tpu.health.detector import Outcome
from dpwa_tpu.parallel.schedules import async_drain_draw

__all__ = ["AsyncExchangeEngine", "register_metrics"]

# Staleness histogram: one bucket per lag 0..max_staleness plus one
# overflow bucket counting bounded-staleness drops.
_OVERFLOW = "overflow"


class AsyncExchangeEngine:
    """Barrier-free round loop over a :class:`TcpTransport`.

    One engine wraps one transport.  The training thread drives
    :meth:`exchange` (host replica) or :meth:`exchange_on_device`
    (device-resident replica) once per local step; fetch slots run on
    daemon threads and never gate a round.

    ``now`` is the telemetry time source.  Default resolution order:
    an explicit argument, then the transport's flowctl estimator's
    ``now`` seam (so one VirtualClock injection governs the whole
    flowctl + async stack), then the production monotonic clock.
    """

    def __init__(self, transport, now: Optional[Callable[[], float]] = None):
        self.t = transport
        cfg = transport.config.protocol.async_rounds
        self.cfg = cfg
        self.me = transport.me
        self.seed = transport.schedule.seed
        if now is None:
            est = getattr(transport, "_estimator", None)
            now = est.now if est is not None else monotonic_now
        self.now: Callable[[], float] = now
        # Arm the transport's publish-clock dedup guard: from here on a
        # publish clock merges at most once per peer, whichever path
        # (prefetch slot, async queue, plain fetch) delivered it.
        transport._async_guard = {}
        transport.async_engine = self
        # Pay the drain-draw's first-call jit compile now, off the round
        # clock (the warm_control_draws rationale, scoped to one draw).
        float(async_drain_draw(self.seed, 0, self.me))
        # -- cross-thread state (slot threads append, training drains) --
        self._lock = threading.Lock()
        # (peer, raw9, launch_step, t_launch, t_land) in arrival order.
        self._arrivals: List[tuple] = []
        self._inflight: Dict[int, dict] = {}
        # -- training-thread state --------------------------------------
        # peer -> deque of (clock, raw9, wire_span_s, t_land) admitted
        # frames, newest clocks kept (queue_depth admission).
        self._pending: Dict[int, deque] = {}
        self._round_stale: List[int] = []
        # -- tallies (under _lock: snapshot runs on healthz threads) ----
        self._rounds = 0
        self._merges = 0
        self._stale_drops = 0
        self._dup_drops = 0
        self._shed = 0
        self._fold_dispatches = 0
        self._fold_frames = 0
        self._pending_wait_s = 0.0
        self._hist: Dict[object, int] = {
            **{lag: 0 for lag in range(int(cfg.max_staleness) + 1)},
            _OVERFLOW: 0,
        }
        self._peer: Dict[int, dict] = {}
        if getattr(transport, "metrics_registry", None) is not None:
            register_metrics(transport.metrics_registry, self)

    # ------------------------------------------------------------------
    # Frame intake
    # ------------------------------------------------------------------

    def _peer_stats(self, peer: int) -> dict:
        s = self._peer.get(peer)
        if s is None:
            s = self._peer[peer] = {
                "merges": 0, "stale": 0, "shed": 0, "last_lag": None,
                "lag_sum": 0, "fails": 0,
            }
        return s

    def _launch(self, peer: int, step: int) -> None:
        """Start a background wire fetch to ``peer`` if none is already
        in flight.  The slot thread only moves bytes (the transport's
        wire/consume split); judgement happens at drain time on the
        training thread."""
        with self._lock:
            if peer in self._inflight:
                return
            slot = {"peer": peer, "step": int(step), "t0": self.now()}
            self._inflight[peer] = slot

        def _run():
            raw = self.t._wire_fetch(peer, step=step)
            t1 = self.now()
            with self._lock:
                self._arrivals.append((peer, raw, step, slot["t0"], t1))
                self._inflight.pop(peer, None)

        th = threading.Thread(
            target=_run, daemon=True,
            name=f"dpwa-async:{self.t.port}",
        )
        slot["thread"] = th
        th.start()

    def offer(self, peer: int, raw: tuple, step: int = 0,
              span_s: float = 0.0) -> None:
        """Hand the engine an already-fetched raw 9-tuple.

        The scripted-arrival entry point: soak tests and harnesses
        deliver frames here under a VirtualClock instead of running live
        fetch slots, which is what makes the full soak bit-identical
        across reruns."""
        t1 = self.now()
        with self._lock:
            self._arrivals.append((peer, raw, int(step), t1 - span_s, t1))

    def _collect(self, step: int) -> List[tuple]:
        """Move completed arrivals into the pending queues.

        Admission is a pure function of publish clocks: failed fetches
        bypass the queue (returned for immediate outcome accounting), a
        clock at or below the peer's last-merged clock is a duplicate
        (counted, recorded ``stale`` at drain), and a full queue sheds
        its OLDEST clock — the frame that would merge at the smallest
        weight anyway.  Returns the list of failure/duplicate arrivals
        to account this round."""
        with self._lock:
            arrivals, self._arrivals = self._arrivals, []
        charge: List[tuple] = []
        guard = self.t._async_guard or {}
        for peer, raw, launch_step, t0, t1 in arrivals:
            got = raw[1]
            if got is None:
                charge.append((peer, raw, t1))
                continue
            clock = float(got[1])
            merged_ck = guard.get(int(raw[0]))
            if merged_ck is not None and clock <= merged_ck:
                with self._lock:
                    self._dup_drops += 1
                charge.append((peer, raw, t1))
                continue
            dq = self._pending.get(peer)
            if dq is None:
                dq = self._pending[peer] = deque()
            dq.append((clock, raw, max(t1 - t0, 0.0), t1))
            if len(dq) > int(self.cfg.queue_depth):
                # Shed the smallest publish clock in the queue.
                oldest = min(range(len(dq)), key=lambda i: (dq[i][0], i))
                del dq[oldest]
                with self._lock:
                    self._shed += 1
                self._peer_stats(peer)["shed"] += 1
        return charge

    # ------------------------------------------------------------------
    # Drain + merge
    # ------------------------------------------------------------------

    def _drain_order(self, clock: float, step: int) -> List[tuple]:
        """Flatten the pending queues into the deterministic drain
        order: lag-ascending (freshest merges first, so the best
        information lands before maximally-damped stragglers), with
        equal-lag ties rotated by the ``async_drain_draw`` stream and
        finally broken by peer index.  Pure function of publish clocks
        and the registered threefry tag — two reruns with the same
        pending sets drain identically."""
        cands: List[tuple] = []
        for peer in sorted(self._pending):
            dq = self._pending[peer]
            while dq:
                ck, raw, span, t_land = dq.popleft()
                lag = max(int(clock) - int(ck), 0)
                draw = async_drain_draw(self.seed, step, peer)
                cands.append((lag, draw, peer, ck, raw, span, t_land))
        cands.sort(key=lambda c: (c[0], c[1], c[2], -c[3]))
        return cands

    def _charge_failures(self, charge: List[tuple], step: int) -> None:
        """Record failed/duplicate arrivals against the consuming round:
        failures run the ordinary consume leg (scoreboard + estimator
        accounting); duplicates record the soft ``stale`` outcome
        directly (the dedup guard would classify them anyway, but a
        second consume would re-run the guard/trust screens on bytes
        that already merged)."""
        sb = self.t.scoreboard
        for peer, raw, _t1 in charge:
            if raw[1] is None:
                self.t._consume_fetch(raw, step)
                self._peer_stats(int(raw[0]))["fails"] += 1
            elif sb is not None:
                sb.record(
                    int(raw[0]), Outcome.STALE,
                    latency_s=float(raw[3]), nbytes=int(raw[4]),
                    round=step,
                )

    def _drop_stale(self, peer: int, raw: tuple, lag: int,
                    step: int) -> None:
        """The bounded-staleness drop rule: record the soft ``stale``
        outcome (degrade, never quarantine) and count the overflow
        bucket; the frame's bytes are never screened or merged."""
        with self._lock:
            self._stale_drops += 1
            self._hist[_OVERFLOW] += 1
        st = self._peer_stats(peer)
        st["stale"] += 1
        st["last_lag"] = int(lag)
        self._round_stale.append(peer)
        if self.t.scoreboard is not None:
            self.t.scoreboard.record(
                peer, Outcome.STALE,
                latency_s=float(raw[3]), nbytes=int(raw[4]), round=step,
            )

    def _consume(self, raw: tuple, clock: float, loss: float, step: int,
                 lag: int):
        """Run the transport's consume leg on one pending frame and
        weigh it, composing the staleness damping into alpha.  Returns
        ``(remote_vec, damped_alpha)`` or ``None`` when the frame failed
        a screen (guard/trust/dedup — outcome already recorded)."""
        got = self.t._consume_fetch(raw, step)
        if got is None:
            return None
        remote_vec, alpha = self.t._weigh_remote(got, clock, loss)
        damped = float(alpha) * float(self.cfg.staleness_damping) ** int(lag)
        return remote_vec, damped

    def _note_merge(self, peer: int, lag: int, t_land: float) -> None:
        wait = max(self.now() - t_land, 0.0)
        with self._lock:
            self._merges += 1
            self._hist[int(lag)] = self._hist.get(int(lag), 0) + 1
            self._pending_wait_s += wait
        st = self._peer_stats(peer)
        st["merges"] += 1
        st["last_lag"] = int(lag)
        st["lag_sum"] += int(lag)

    def exchange(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> Tuple[np.ndarray, List[Tuple[int, float, int]]]:
        """One barrier-free round on a HOST replica.

        Publish, collect completed arrivals, launch this step's schedule
        partner fetch (if idle), then merge every pending frame that
        survives the drop rule — in the deterministic drain order, each
        through the full consume leg, dense or sparse or shard alike
        (shard frames lerp only their slice via ``_merge_remote``).
        Never blocks on an in-flight stream.

        Returns ``(merged_vec, merges)`` with ``merges`` the drain-
        ordered list of ``(peer, damped_alpha, lag)`` actually applied.
        """
        try:
            self.t.publish(vec, clock, loss)
            with self._lock:
                self._rounds += 1
            charge = self._collect(clock)
            sched, partner, remapped = self.t._resolve_partner(step)
            self.t.last_round = {
                "step": step, "sched_partner": sched, "partner": partner,
                "remapped": remapped, "outcome": None,
            }
            if partner != self.me and self.t.schedule.participates(
                step, self.me
            ):
                self._launch(partner, step)
            self._charge_failures(charge, step)
            merges: List[Tuple[int, float, int]] = []
            out = np.asarray(vec, dtype=np.float32)
            for lag, _draw, peer, _ck, raw, _span, t_land in (
                self._drain_order(clock, step)
            ):
                if lag > int(self.cfg.max_staleness):
                    self._drop_stale(peer, raw, lag, step)
                    continue
                res = self._consume(raw, clock, loss, step, lag)
                if peer == partner:
                    self.t.last_round["outcome"] = (
                        self.t.last_fetch.get("outcome")
                    )
                if res is None:
                    self._peer_stats(peer)["fails"] += 1
                    continue
                remote_vec, damped = res
                out = self.t._merge_remote(out, remote_vec, damped)
                self._note_merge(peer, lag, t_land)
                merges.append((peer, damped, lag))
            return out, merges
        finally:
            self.t._membership_end_round(step)

    def exchange_on_device(
        self, vec_dev, clock: float, loss: float, step: int
    ):
        """One barrier-free round on a DEVICE-RESIDENT replica.

        Same intake/drop/drain discipline as :meth:`exchange`; accepted
        frames become ``(kind, payload, peer, alpha)`` device frames and
        — with ``async_rounds.fold`` on — consecutive dense frames in
        the drain order batch through the merge engine's single
        ``fold`` dispatch (bit-identical to sequential merges).  Sparse
        frames stay sparse across the seam (``_sparse_consume``), so
        shard slices splice in-kernel with no host densify.

        Returns ``(merged_device_vec, merges)``."""
        from dpwa_tpu.device import DeviceReplica, default_engine

        eng = default_engine()
        t = self.t
        rep = t._dev_replica
        if rep is None or rep.dev is not vec_dev:
            rep = DeviceReplica(vec_dev)
            t._dev_replica = rep
        try:
            t.publish(rep.host(), clock, loss)
            with self._lock:
                self._rounds += 1
            charge = self._collect(clock)
            sched, partner, remapped = t._resolve_partner(step)
            t.last_round = {
                "step": step, "sched_partner": sched, "partner": partner,
                "remapped": remapped, "outcome": None,
            }
            if partner != self.me and t.schedule.participates(
                step, self.me
            ):
                self._launch(partner, step)
            self._charge_failures(charge, step)
            frames: List[tuple] = []
            merges: List[Tuple[int, float, int]] = []
            t._sparse_consume = True
            try:
                for lag, _draw, peer, _ck, raw, _span, t_land in (
                    self._drain_order(clock, step)
                ):
                    if lag > int(self.cfg.max_staleness):
                        self._drop_stale(peer, raw, lag, step)
                        continue
                    res = self._consume(raw, clock, loss, step, lag)
                    if res is None:
                        self._peer_stats(peer)["fails"] += 1
                        continue
                    remote_vec, damped = res
                    frames.append(
                        t._classify_device_frame(remote_vec, peer, damped)
                    )
                    self._note_merge(peer, lag, t_land)
                    merges.append((peer, damped, lag))
            finally:
                t._sparse_consume = False
            merged = t._apply_device_frames(
                eng, rep.dev, frames, fold=bool(self.cfg.fold)
            )
            if frames and self.cfg.fold:
                # Fold accounting: runs of >=2 consecutive dense frames
                # went through a single batched dispatch.
                runs: List[int] = []
                n = 0
                for f in frames:
                    if f[0] == "dense":
                        n += 1
                    elif n:
                        runs.append(n)
                        n = 0
                if n:
                    runs.append(n)
                with self._lock:
                    self._fold_dispatches += sum(
                        1 for r in runs if r >= 2
                    )
                    self._fold_frames += sum(r for r in runs if r >= 2)
            eng.note_round()
            if merged is not rep.dev:
                rep.swap(merged)
            return merged, merges
        finally:
            t._membership_end_round(step)

    # ------------------------------------------------------------------
    # Plane integration
    # ------------------------------------------------------------------

    def pop_round_stale(self) -> List[int]:
        """Drain the peers dropped stale this round (incident plane)."""
        out, self._round_stale = self._round_stale, []
        return out

    def pending_depth(self, peer: int) -> int:
        dq = self._pending.get(peer)
        return len(dq) if dq is not None else 0

    def join_inflight(self, timeout_s: float = 5.0) -> None:
        """Block until in-flight fetch slots land (test teardown — never
        called on the round path)."""
        with self._lock:
            slots = [self._inflight[p] for p in sorted(self._inflight)]
        for slot in slots:
            th = slot.get("thread")
            if th is not None:
                th.join(timeout_s)

    def snapshot(self) -> dict:
        """JSON-ready async-plane state: the ``async`` sub-document in
        ``health_snapshot`` (schema ``_HEALTH_GROUPS["async"]``)."""
        with self._lock:
            hist = [
                self._hist.get(lag, 0)
                for lag in range(int(self.cfg.max_staleness) + 1)
            ] + [self._hist.get(_OVERFLOW, 0)]
            out = {
                "rounds": self._rounds,
                "merges": self._merges,
                "stale_drops": self._stale_drops,
                "dup_drops": self._dup_drops,
                "shed": self._shed,
                "fold_dispatches": self._fold_dispatches,
                "fold_frames": self._fold_frames,
                "pending_wait_s": round(self._pending_wait_s, 6),
                "max_staleness": int(self.cfg.max_staleness),
                "staleness_damping": float(self.cfg.staleness_damping),
                "queue_depth": int(self.cfg.queue_depth),
                "staleness_hist": hist,
                "inflight": sorted(self._inflight),
            }
        peers = {}
        for p in sorted(self._peer):
            st = self._peer[p]
            n = st["merges"]
            peers[p] = {
                "merges": n,
                "stale": st["stale"],
                "shed": st["shed"],
                "fails": st["fails"],
                "pending": self.pending_depth(p),
                "last_lag": st["last_lag"],
                "mean_lag": round(st["lag_sum"] / n, 3) if n else None,
            }
        out["peers"] = peers
        return out


def register_metrics(registry, engine: "AsyncExchangeEngine") -> None:
    """Expose the async round plane on a MetricsRegistry
    (``dpwa_async_*`` families, the flowctl estimator pattern)."""
    from dpwa_tpu.obs.prometheus import Family

    def collect():
        snap = engine.snapshot()
        merges = Family(
            "dpwa_async_merges_total", "counter",
            "Frames merged by the barrier-free async round loop",
        )
        stale = Family(
            "dpwa_async_stale_drops_total", "counter",
            "Frames dropped by the bounded-staleness rule",
        )
        lag = Family(
            "dpwa_async_peer_last_lag", "gauge",
            "Publish-clock lag of the last frame seen per peer",
        )
        pend = Family(
            "dpwa_async_pending_frames", "gauge",
            "Frames currently queued per peer",
        )
        hist = Family(
            "dpwa_async_staleness_merges", "counter",
            "Merged frames by publish-clock lag (overflow = dropped)",
        )
        for p, info in sorted((snap.get("peers") or {}).items()):
            labels = {"peer": p}
            merges.sample(info.get("merges"), labels)
            stale.sample(info.get("stale"), labels)
            if info.get("last_lag") is not None:
                lag.sample(info.get("last_lag"), labels)
            pend.sample(info.get("pending"), labels)
        buckets = snap.get("staleness_hist") or []
        for i, n in enumerate(buckets):
            label = str(i) if i < len(buckets) - 1 else "overflow"
            hist.sample(n, {"lag": label})
        return [
            merges, stale, lag, pend, hist,
            Family(
                "dpwa_async_rounds_total", "counter",
                "Barrier-free rounds driven",
            ).sample(snap.get("rounds")),
            Family(
                "dpwa_async_fold_frames_total", "counter",
                "Dense frames batched through fold dispatches",
            ).sample(snap.get("fold_frames")),
            Family(
                "dpwa_async_pending_wait_seconds_total", "counter",
                "Cumulative arrival-to-merge wait across merged frames",
            ).sample(snap.get("pending_wait_s")),
        ]

    registry.register(collect)
