"""Structured per-step metrics (JSONL).

The reference logs free-text lines via the ``logging`` module (peer chosen,
α, clocks — SURVEY.md §5 "Metrics/logging").  The rebuild emits structured
records instead: one JSON object per step with loss, exchange partner, α,
participation, bytes moved, and wall-clock timings, to stdout and/or a
JSONL file — greppable and plottable without parsing prose."""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import IO, Any, Mapping, Optional

import numpy as np


def _jsonable(v: Any) -> Any:
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    if hasattr(v, "tolist"):  # jax arrays
        return np.asarray(v).tolist()
    return v


class MetricsLogger:
    """Writes one JSON object per record; stdlib-only, no deps.

    CONTRACT: :meth:`log_exchange` is *deferred* — it holds each record
    until the next logging point, so the final record of a run is only
    written by :meth:`flush` / :meth:`close`.  Call :meth:`close` when
    done, or use the logger as a context manager.  As a safety net an
    ``atexit`` flush is registered, so a forgotten close loses nothing on
    a clean interpreter exit — but records written that late appear after
    anything else the process printed.  Output order is guaranteed:
    every logging point (:meth:`log` or :meth:`log_exchange`) first
    writes any pending deferred record, so records always land in the
    order they were produced — just one logging interval late, with
    their original ``step``/``t`` stamps."""

    def __init__(
        self,
        path: Optional[str] = None,
        stream: Optional[IO[str]] = None,
        every: int = 1,
        max_bytes: int = 0,
        keep: int = 1,
    ):
        self._path = path
        self._file = open(path, "a", encoding="utf-8") if path else None
        self._stream = stream
        self.every = max(1, every)
        # Size cap for the JSONL file: when the next record would push it
        # past ``max_bytes`` the current file rolls into a ``<path>.1`` …
        # ``<path>.keep`` cascade (``.i`` shifts to ``.i+1``, the oldest
        # roll is replaced) and a fresh file starts — a soak run keeps at
        # most ~(keep+1)x max_bytes on disk instead of growing
        # unboundedly, and ``keep`` large enough covers the incident
        # window a post-mortem needs.  0 = unbounded (the historical
        # behaviour); keep=1 = the historical single-roll behaviour.
        self.max_bytes = max(0, int(max_bytes))
        self.keep = max(1, int(keep))
        self._t0 = time.perf_counter()
        self._pending = None
        # Guards the _pending handoff: log_exchange (training thread)
        # parks the deferred record, while ANY logging point — including
        # log_event from an Rx/healthz thread — may pop it.  Without the
        # lock two concurrent poppers could both pass the None check and
        # write the record twice.  Separate from _write_lock because
        # flush() re-enters log() → _write() and the locks are
        # non-reentrant.
        self._pending_lock = threading.Lock()
        # Serializes writers: the training thread and any Rx/healthz
        # thread logging events through the same logger must not
        # interleave mid-rotation (torn lines, double-rolls).
        self._write_lock = threading.Lock()
        self._atexit = atexit.register(self.flush)

    # dpwalint: guarded_by(_write_lock)
    def _rotate(self) -> None:
        """Roll ``<path>`` into the ``.1`` … ``.keep`` cascade.

        Only ever called from ``_write`` with ``_write_lock`` held."""
        try:
            self._file.close()
            for i in range(self.keep - 1, 0, -1):
                older = f"{self._path}.{i}"
                if os.path.exists(older):
                    os.replace(older, f"{self._path}.{i + 1}")
            os.replace(self._path, self._path + ".1")
        except OSError:
            pass
        self._file = open(self._path, "a", encoding="utf-8")

    def _write(self, line: str) -> None:
        with self._write_lock:
            if self._file is not None:
                if self.max_bytes and self._path:
                    try:
                        pos = self._file.tell()
                    except OSError:
                        pos = 0
                    if pos and pos + len(line) + 1 > self.max_bytes:
                        self._rotate()
                self._file.write(line + "\n")
                self._file.flush()
            if self._stream is not None:
                print(line, file=self._stream, flush=True)

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def log(self, step: int, _t: Optional[float] = None, **fields: Any) -> None:
        if step % self.every != 0:
            return
        # Keep file order == production order: a deferred exchange record
        # from an earlier step must land before this one.  (flush() pops
        # _pending before re-entering log(), so this never recurses.)
        self.flush()
        rec: dict[str, Any] = {
            "step": int(step),
            "t": round(
                (time.perf_counter() - self._t0) if _t is None else _t, 4
            ),
        }
        for k, v in fields.items():
            rec[k] = _jsonable(v)
        self._write(json.dumps(rec))

    def elapsed(self) -> float:
        """Seconds since this logger was created (the ``t`` clock)."""
        return time.perf_counter() - self._t0

    def log_exchange(
        self,
        step: int,
        losses,
        info,
        payload_bytes: int,
        t: Optional[float] = None,
        **extra: Any,
    ) -> None:
        """Convenience: the standard gossip-round record — **deferred**.

        Materializing a device value mid-stream blocks on the whole
        in-flight dispatch pipeline, and that sync can dominate the loop
        when device↔host latency is high next to a sub-ms train step.
        So this method
        never blocks: on non-logging steps it returns without touching
        ``losses``/``info`` at all; on logging steps it starts async
        device→host copies and WRITES THE RECORD AT THE NEXT LOGGING
        POINT (or :meth:`close`), by which time the data has long
        arrived.  Records therefore appear one logging interval late,
        with their original ``step``/``t`` stamps.

        ``t`` overrides the record's time stamp (seconds on the
        :meth:`elapsed` clock) — for callers that buffer records
        themselves and replay them after a timed region."""
        if step % self.every != 0:
            return
        for arr in (losses, info.partner, info.alpha, info.participated):
            if hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()
        self.flush()
        with self._pending_lock:
            self._pending = (
                step,
                self.elapsed() if t is None else t,
                losses,
                info,
                payload_bytes,
                extra,
            )

    def log_health(
        self, step: int, snapshot: Mapping[str, Any], **extra: Any
    ) -> None:
        """One health record from a scoreboard snapshot
        (:meth:`dpwa_tpu.parallel.tcp.TcpTransport.health_snapshot`).

        Flattens the per-peer dict into parallel lists keyed by ``peer``
        so downstream tooling (tools/health_report.py, jq one-liners)
        can read columns without walking nested objects:

        - ``peer_state`` — scoreboard state per remote peer;
        - ``suspicion`` — detector suspicion score per remote peer;
        - ``quarantined_rounds`` — lifetime rounds spent quarantined;
        - ``trust`` / ``trust_damped`` / ``trust_rejected`` — the trust
          plane's per-peer EWMA and verdict counters (present only when
          the content-trust plane contributed to the snapshot);
        - ``deadline_ms`` / ``hedges`` / ``hedge_wins`` / ``busy`` /
          ``slow`` — the flowctl plane's per-peer adaptive deadline and
          hedge/soft-outcome counters, plus top-level ``hedge_rate`` and
          ``shed_total`` (present only when flowctl contributed);
        - ``wire_codec`` / ``wire_bytes`` / ``compression_ratio`` and
          ``overlap_occupancy`` / ``overlap_hidden_frac`` /
          ``overlap_prefetched`` / ``overlap_straddled`` — the wire
          plane's codec accounting and prefetch-overlap view (present
          only when the topk codec or the prefetch pipeline is on);
        - ``copies_per_frame`` / ``ring_occupancy`` — the zero-copy
          frame path's decode-copy tally and receive-ring occupancy
          (ride the wire group when the snapshot carries them);
        - ``device_rounds`` / ``jit_cache_hits`` / ``jit_cache_misses``
          / ``device_dispatches_per_round`` / ``h2d_zero_copy_frac`` /
          ``fold_frames`` — the device merge engine's jit-cache and
          dispatch accounting (present only once a device-resident
          exchange has served a round, docs/device.md);
        - ``view_active`` / ``view_passive`` / ``view_tracked`` /
          ``view_capped`` / ``view_digest_entries`` /
          ``view_digest_bytes`` / ``view_evicted_dead`` /
          ``view_evicted_cap`` / ``view_promotions`` /
          ``view_shuffles`` — the bounded partial-view plane's sizes,
          residency, per-frame digest footprint, and evictions by cause
          (present only under ``membership.view``, docs/membership.md);
        - ``disagreement_rms`` / ``disagreement_rel`` / ``sketch_peers``
          — the obs plane's sketch-based ring-disagreement estimate
          (present only when ``obs.sketch`` is on);
        - ``reactor_loop_lag_ms`` / ``reactor_ready_depth`` /
          ``reactor_open`` / ``reactor_evicted`` /
          ``reactor_busy_shed`` — the reactor Rx scheduler's loop and
          connection accounting (present only under
          ``protocol.rx_server: reactor``);
        - ``async_rounds`` / ``async_merges`` / ``async_stale_drops``
          / ``async_dup_drops`` / ``async_shed`` /
          ``async_fold_frames`` / ``async_staleness_hist`` and the
          per-peer ``async_peer_merges`` / ``async_peer_stale`` /
          ``async_peer_pending`` / ``async_peer_lag`` — the barrier-
          free async round loop's merge/drop/queue accounting (present
          only under ``protocol.async_rounds``, docs/async.md);

        plus attempt/success/quarantine counters.  Obeys ``every`` like
        every other record; written immediately (health snapshots are
        plain host dicts — nothing to defer)."""
        if step % self.every != 0:
            return
        peers = snapshot.get("peers", {})
        order = sorted(peers)
        cols = lambda key: [peers[p].get(key) for p in order]  # noqa: E731
        membership = snapshot.get("membership")
        if membership is not None:
            # Membership view rides the same record: the merged-view
            # incarnation column plus the node's own component/quorum
            # state (scoreboards without an attached MembershipManager
            # produce records byte-identical to the pre-membership ones).
            extra = dict(
                extra,
                incarnation=cols("incarnation"),
                own_incarnation=membership.get("incarnation"),
                component=membership.get("component"),
                component_id=membership.get("component_id"),
                partition_state=membership.get("partition_state"),
            )
        if order and "trust" in peers[order[0]]:
            # Trust columns ride the same record (absent without the
            # trust plane, keeping pre-trust records byte-identical).
            extra = dict(
                extra,
                trust=cols("trust"),
                trust_verdict=cols("trust_verdict"),
                trust_damped=cols("trust_damped"),
                trust_rejected=cols("trust_rejected"),
            )
        flowctl = snapshot.get("flowctl")
        if flowctl is not None and order:
            # Flowctl columns ride the same record (absent without the
            # flow-control plane, keeping earlier records byte-identical).
            hedges = flowctl.get("hedges", 0)
            admission = flowctl.get("admission") or {}
            extra = dict(
                extra,
                deadline_ms=cols("deadline_ms"),
                hedges=cols("hedges"),
                hedge_wins=cols("hedge_wins"),
                busy=cols("busy"),
                slow=cols("slow"),
                hedge_rate=(
                    round(flowctl.get("hedge_wins", 0) / hedges, 4)
                    if hedges
                    else 0.0
                ),
                shed_total=admission.get("shed_total", 0),
            )
        wire = snapshot.get("wire")
        if wire is not None:
            # Wire-plane columns (absent without the topk codec or the
            # prefetch pipeline, keeping dense sequential records
            # byte-identical): which codec published, the honest
            # wire-vs-dense byte ratio, and — under prefetch — how much
            # of the fetch wall-time the pipeline hid under compute.
            extra = dict(
                extra,
                wire_codec=wire.get("codec"),
                wire_bytes=wire.get("wire_bytes"),
                compression_ratio=wire.get("compression_ratio"),
            )
            if wire.get("copies_per_frame") is not None:
                # Zero-copy columns (docs/transport.md): mean payload-
                # sized copies per decoded frame (0.0 = views straight
                # out of the receive ring) and the fraction of ring
                # bytes currently leased out.
                extra = dict(
                    extra,
                    copies_per_frame=wire.get("copies_per_frame"),
                    ring_occupancy=wire.get("ring_occupancy"),
                )
            overlap = wire.get("overlap")
            if overlap is not None:
                extra = dict(
                    extra,
                    overlap_occupancy=overlap.get("occupancy"),
                    overlap_hidden_frac=overlap.get("hidden_frac"),
                    overlap_prefetched=overlap.get("prefetched"),
                    overlap_straddled=overlap.get("straddled"),
                )
            device = wire.get("device")
            if device is not None and device.get("device_rounds"):
                # Device merge engine columns (docs/device.md; absent
                # until a device-resident exchange has served a round,
                # keeping host-only records byte-identical): jit-cache
                # health, fused dispatches per round, and the fraction
                # of host→device crossings that were pointer adoptions.
                extra = dict(
                    extra,
                    device_rounds=device.get("device_rounds"),
                    jit_cache_hits=device.get("jit_cache_hits"),
                    jit_cache_misses=device.get("jit_cache_misses"),
                    device_dispatches_per_round=device.get(
                        "device_dispatches_per_round"
                    ),
                    h2d_zero_copy_frac=device.get("h2d_zero_copy_frac"),
                    fold_frames=device.get("fold_frames"),
                )
            view = wire.get("view")
            if view is not None:
                # Partial-view columns (docs/membership.md; absent
                # without membership.view, keeping global-view records
                # byte-identical): view sizes, tracked residency vs the
                # state cap, digest entries/bytes per frame, and the
                # eviction tally split by cause (dead vs LRU cap).
                extra = dict(
                    extra,
                    view_active=view.get("view_active"),
                    view_passive=view.get("view_passive"),
                    view_tracked=view.get("view_tracked"),
                    view_capped=view.get("view_capped"),
                    view_digest_entries=view.get("view_digest_entries"),
                    view_digest_bytes=view.get("view_digest_bytes"),
                    view_evicted_dead=view.get("view_evicted_dead"),
                    view_evicted_cap=view.get("view_evicted_cap"),
                    view_promotions=view.get("view_promotions"),
                    view_shuffles=view.get("view_shuffles"),
                )
            shard = wire.get("shard")
            if shard is not None:
                # Sharded-wire columns (absent at shard.k == 1, keeping
                # unsharded records byte-identical): the shard count and
                # the round-robin coverage (distinct shards served / k,
                # 1.0 once every shard has crossed the wire).
                extra = dict(
                    extra,
                    shard_k=shard.get("k"),
                    shard_coverage=shard.get("coverage"),
                )
        reactor = snapshot.get("reactor")
        if reactor is not None:
            # Reactor scheduler columns (absent under the threaded Rx
            # server, keeping those records byte-identical): the event
            # loop's saturation signal plus its connection accounting.
            extra = dict(
                extra,
                reactor_loop_lag_ms=reactor.get("loop_lag_ms"),
                reactor_ready_depth=reactor.get("ready_depth"),
                reactor_open=reactor.get("open"),
                reactor_evicted=reactor.get("evicted"),
                reactor_busy_shed=reactor.get("busy_shed"),
            )
        obs = snapshot.get("obs")
        if obs is not None:
            # Observability columns (absent without the obs plane,
            # keeping earlier records byte-identical): the sketch-based
            # ring-disagreement estimate described in docs/observability.md.
            conv = obs.get("convergence")
            if conv is not None:
                extra = dict(
                    extra,
                    disagreement_rms=conv.get("rms"),
                    disagreement_rel=conv.get("rel_rms"),
                    sketch_peers=conv.get("peers_seen"),
                )
        tune = snapshot.get("tune")
        if tune is not None and order:
            # Self-tuning wire columns (absent without tune.enabled,
            # keeping static-wire records byte-identical): the EFFECTIVE
            # ladder rung/codec each tracked link publishes at (None for
            # peers the controller has not yet observed), the DEGRADED
            # shed flags, and the ladder's lifetime traffic counters —
            # dwell_violations is the hysteresis invariant (always 0).
            links = tune.get("links") or {}
            tcol = lambda key: [  # noqa: E731
                links.get(p, {}).get(key) for p in order
            ]
            extra = dict(
                extra,
                tune_rung=tcol("effective_rung"),
                tune_codec=tcol("codec"),
                tune_shed=tcol("shed_active"),
                tune_escalations=tune.get("escalations"),
                tune_backoffs=tune.get("backoffs"),
                tune_sheds=tune.get("sheds"),
                tune_dwell_violations=tune.get("dwell_violations"),
            )
        async_snap = snapshot.get("async")
        if async_snap is not None and order:
            # Async round-loop columns (absent under lock-step rounds,
            # keeping those records byte-identical): cumulative merge/
            # drop/queue tallies, the staleness histogram (buckets
            # 0..max_staleness plus overflow = drops), and the per-peer
            # view aligned to the record's ``peer`` column.
            apeers = async_snap.get("peers") or {}
            acol = lambda key, d: [  # noqa: E731
                apeers.get(p, {}).get(key, d) for p in order
            ]
            extra = dict(
                extra,
                async_rounds=async_snap.get("rounds"),
                async_merges=async_snap.get("merges"),
                async_stale_drops=async_snap.get("stale_drops"),
                async_dup_drops=async_snap.get("dup_drops"),
                async_shed=async_snap.get("shed"),
                async_fold_frames=async_snap.get("fold_frames"),
                async_staleness_hist=async_snap.get("staleness_hist"),
                async_peer_merges=acol("merges", 0),
                async_peer_stale=acol("stale", 0),
                async_peer_pending=acol("pending", 0),
                async_peer_lag=acol("last_lag", None),
            )
        self.log(
            step,
            record="health",
            me=snapshot.get("me"),
            round=snapshot.get("round"),
            peer=[int(p) for p in order],
            peer_state=cols("state"),
            suspicion=cols("suspicion"),
            quarantined_rounds=cols("quarantined_rounds"),
            quarantines=cols("quarantines"),
            attempts=cols("attempts"),
            failures=cols("failures"),
            probe_attempts=cols("probe_attempts"),
            last_outcome=cols("last_outcome"),
            **extra,
        )

    def log_loss(
        self,
        step: int,
        loss: float,
        me: int,
        epoch: Optional[int] = None,
        alpha: Optional[float] = None,
        partner: Optional[int] = None,
        outcome: Optional[str] = None,
        test_loss: Optional[float] = None,
        test_acc: Optional[float] = None,
        _t: Optional[float] = None,
    ) -> None:
        """One ``record: "loss"`` row — the training harness's per-step
        loss stream (docs/training.md).

        The schema is CLOSED (tools/schema_check.py): only the merge
        metadata that the loss/incident join consumes rides along, so
        the record stays diffable across runs and planes.  Obeys
        ``every`` like ordinary records; the harness additionally
        applies ``run.loss_every`` before calling.  ``_t`` overrides the
        time stamp — the harness passes its VirtualClock so seeded
        reruns produce byte-identical rows."""
        fields: dict[str, Any] = {"record": "loss", "me": int(me)}
        fields["loss"] = float(loss)
        if epoch is not None:
            fields["epoch"] = int(epoch)
        if alpha is not None:
            fields["alpha"] = float(alpha)
        if partner is not None:
            fields["partner"] = int(partner)
        if outcome is not None:
            fields["outcome"] = str(outcome)
        if test_loss is not None:
            fields["test_loss"] = float(test_loss)
        if test_acc is not None:
            fields["test_acc"] = float(test_acc)
        self.log(step, _t=_t, **fields)

    def log_run(
        self, step: int, me: int, leg: str, status: str, peers: int,
        seed: int, _t: Optional[float] = None, **fields: Any,
    ) -> None:
        """One ``record: "run"`` envelope row (docs/training.md).

        ``status: "start"`` opens a node's stream with the leg shape;
        exactly one terminal ``"done"``/``"crashed"`` row carries the
        outcome fields ``tools/run_report.py`` consumes.  Bypasses ``every``: an envelope row dropped to a
        sampling interval would orphan the whole stream."""
        self.flush()
        rec: dict[str, Any] = {
            "step": int(step),
            "t": round(
                (time.perf_counter() - self._t0) if _t is None else _t, 4
            ),
            "record": "run",
            "me": int(me),
            "leg": str(leg),
            "status": str(status),
            "peers": int(peers),
            "seed": int(seed),
        }
        for k, v in fields.items():
            rec[k] = _jsonable(v)
        self._write(json.dumps(rec))

    # dpwalint: thread_root(rx)
    def log_event(self, step: int, event: str, **fields: Any) -> None:
        """One recovery/control-plane event record, written immediately.

        Events are rare and load-bearing (rollback, bootstrap, resync,
        poisoned rejection) so they bypass ``every`` — dropping one to a
        sampling interval would hide the exact evidence
        ``tools/health_report.py`` summarizes.  The record carries
        ``record: "event"`` and ``event: <kind>`` so downstream tooling
        can fold all kinds with one filter."""
        self.flush()
        rec: dict[str, Any] = {
            "step": int(step),
            "t": round(time.perf_counter() - self._t0, 4),
            "record": "event",
            "event": str(event),
        }
        for k, v in fields.items():
            rec[k] = _jsonable(v)
        self._write(json.dumps(rec))

    def log_tune(self, step: int, decision: Mapping[str, Any]) -> None:
        """One self-tuning-wire ladder decision (``record: "tune"``),
        written immediately.

        Decisions are rare and load-bearing like events (an escalation
        explains every compressed frame after it; a dwell-window replay
        is the determinism test's fixture) so they bypass ``every``.
        The schema is CLOSED (tools/schema_check.py): exactly the
        fields LinkTuner._record emits, so seeded reruns diff to empty
        on the whole decision log."""
        self.flush()
        rec: dict[str, Any] = {
            "step": int(step),
            "t": round(time.perf_counter() - self._t0, 4),
            "record": "tune",
        }
        for k, v in decision.items():
            rec[k] = _jsonable(v)
        self._write(json.dumps(rec))

    def flush(self) -> None:
        """Write the deferred record, if any (blocks only on its arrays)."""
        with self._pending_lock:
            pending, self._pending = self._pending, None
        if pending is None:
            return
        step, t, losses, info, payload_bytes, extra = pending
        alpha = np.asarray(info.alpha)
        part = np.asarray(info.participated)
        self.log(
            step,
            _t=t,
            loss_mean=float(np.asarray(losses).mean()),
            losses=losses,
            partner=info.partner,
            alpha=alpha,
            participated=part,
            exchanged_bytes=int(payload_bytes * int(part.sum())),
            **extra,
        )

    def close(self) -> None:
        self.flush()
        atexit.unregister(self.flush)
        with self._write_lock:
            if self._file is not None:
                self._file.close()
                self._file = None
