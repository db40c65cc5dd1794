"""Round-trace spans: where does a round's wall time actually go?

The exchange hot path (dpwa_tpu/parallel/tcp.py) is a fixed pipeline —
partner draw, wire leg, decode, guard, trust screen, merge, publish,
plus the prefetch join — so a general-purpose span tree is overkill.
A round trace here is one flat JSONL record: stage name → accumulated
seconds, plus the identifiers needed to join it across peers.

Records (written through :class:`~dpwa_tpu.metrics.MetricsLogger`, so
they share the JSONL conventions of every other stream):

- ``{"record": "trace", "kind": "round", "me", "step", "trace_id",
  "remote_trace_id", "partner", "outcome", "stages": {...}, ...}`` —
  one per traced exchange on the *fetching* node.  ``trace_id`` is the
  id this node published this round (``"{me}:{seq}"``); the frame it
  fetched carried the partner's id, recorded as ``remote_trace_id``.
- ``{"record": "trace", "kind": "serve", "me", "trace_id", "nbytes",
  "dur_s"}`` — one per served frame on the *serving* node, stamped with
  the id of the frame it pushed onto the wire.

Joining ``round.remote_trace_id`` to ``serve.trace_id`` across the
per-node files reconstructs the full cross-peer timeline of a round —
``tools/trace_report.py`` does exactly that.

Allocation discipline: ``begin_round`` creates one dict per traced
round; ``mark``/``set`` mutate it in place; nothing is formatted until
``end_round``.  When no round is active every hook is a dict-lookup
no-op, and the transport never calls ``perf_counter`` for tracing
unless the tracer exists — so ``obs.trace=false`` stays zero-cost.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional

from dpwa_tpu.metrics import MetricsLogger

# Bounded per-stage duration windows backing stage_summary() medians.
_STAGE_WINDOW = 512


class Tracer:
    """Per-node round tracer (see module doc).

    ``begin_round``/``mark``/``set``/``end_round`` run on the training
    thread; ``note_serve`` runs on Rx connection threads; summaries are
    read by healthz/metrics threads — hence the lock around everything
    shared.  The current-round dict itself is training-thread-only.
    """

    def __init__(
        self,
        me: int,
        every: int = 1,
        path: Optional[str] = None,
        max_records: int = 4096,
    ):
        self.me = int(me)
        self.every = max(1, int(every))
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=max(1, int(max_records)))
        self._stage_win: Dict[str, deque] = {}
        self._stage_n: Dict[str, int] = {}
        self._stage_total: Dict[str, float] = {}
        self._cur: Optional[dict] = None
        self._pending_serve: deque = deque(maxlen=4096)
        self._logger = MetricsLogger(path=path) if path else None

    # -- round lifecycle (training thread) --------------------------------

    @property
    def active(self) -> bool:
        return self._cur is not None

    def begin_round(self, step: int) -> bool:
        """Start tracing ``step`` (subject to ``every`` sampling)."""
        if step % self.every != 0:
            return False
        self._cur = {
            "record": "trace",
            "kind": "round",
            "me": self.me,
            "step": int(step),
            "stages": {},
        }
        return True

    def mark(self, stage: str, dur_s: float) -> None:
        """Accumulate ``dur_s`` into ``stage`` of the current round."""
        cur = self._cur
        if cur is None:
            return
        st = cur["stages"]
        st[stage] = st.get(stage, 0.0) + dur_s
        self._note_stage(stage, dur_s)

    def set(self, **fields: Any) -> None:
        """Attach identifier/outcome fields to the current round."""
        cur = self._cur
        if cur is None:
            return
        for k, v in fields.items():
            if v is not None:
                cur[k] = v

    def end_round(self, **fields: Any) -> None:
        cur, self._cur = self._cur, None
        if cur is None:
            return
        for k, v in fields.items():
            if v is not None:
                cur[k] = v
        cur["stages"] = {
            k: round(v, 6) for k, v in cur["stages"].items()
        }
        # Serve spans collected during this round land first, so the
        # JSONL stays roughly chronological.
        self._drain_serves()
        self._emit(cur)

    # -- serve side (Rx connection threads) --------------------------------

    # dpwalint: thread_root(rx)
    def note_serve(self, trace_id: str, nbytes: int, dur_s: float) -> None:
        """One span per served frame, stamped with the frame's trace id.

        Runs on an Rx connection thread while the fetcher on the other
        end is mid-``recv``, so it does the absolute minimum under the
        shared lock — append a raw tuple.  Record building and logger
        I/O happen when the training thread drains (``end_round`` /
        ``pop_records`` / ``stage_summary`` / ``close``); doing them
        here measurably extends the very wire leg being traced."""
        with self._lock:
            self._pending_serve.append((trace_id, int(nbytes), dur_s))

    def _drain_serves(self) -> None:
        with self._lock:
            if not self._pending_serve:
                return
            pending = list(self._pending_serve)
            self._pending_serve.clear()
        for trace_id, nbytes, dur_s in pending:
            self._note_stage("serve", dur_s)
            self._emit(
                {
                    "record": "trace",
                    "kind": "serve",
                    "me": self.me,
                    "trace_id": trace_id,
                    "nbytes": nbytes,
                    "dur_s": round(dur_s, 6),
                }
            )

    # -- output ------------------------------------------------------------

    def _emit(self, rec: dict) -> None:
        with self._lock:
            self._records.append(rec)
            if self._logger is not None:
                # Step for the logger's sampling/stamp: the round step,
                # or the served frame's seq (from "origin:seq").
                step = rec.get("step")
                if step is None:
                    try:
                        step = int(str(rec.get("trace_id")).split(":")[1])
                    except (IndexError, ValueError):
                        step = 0
                self._logger.log(
                    step, **{k: v for k, v in rec.items() if k != "step"}
                )

    def _note_stage(self, stage: str, dur_s: float) -> None:
        with self._lock:
            win = self._stage_win.get(stage)
            if win is None:
                win = self._stage_win[stage] = deque(maxlen=_STAGE_WINDOW)
                self._stage_n[stage] = 0
                self._stage_total[stage] = 0.0
            win.append(dur_s)
            self._stage_n[stage] += 1
            self._stage_total[stage] += dur_s

    def pop_records(self) -> List[dict]:
        """Drain the in-memory record buffer (tests, adapters)."""
        self._drain_serves()
        with self._lock:
            out = list(self._records)
            self._records.clear()
        return out

    def stage_summary(self) -> Dict[str, dict]:
        """Per-stage ``{n, median_ms, mean_ms, total_s}`` over the recent
        window — the span breakdown behind the /metrics gauges."""
        out: Dict[str, dict] = {}
        self._drain_serves()
        with self._lock:
            for stage in sorted(self._stage_win):
                vals = sorted(self._stage_win[stage])
                if not vals:
                    continue
                n = self._stage_n[stage]
                total = self._stage_total[stage]
                out[stage] = {
                    "n": n,
                    "median_ms": round(vals[len(vals) // 2] * 1e3, 4),
                    "mean_ms": round(total / n * 1e3, 4),
                    "total_s": round(total, 6),
                }
        return out

    def close(self) -> None:
        self._drain_serves()
        if self._logger is not None:
            self._logger.close()
            self._logger = None
