#!/usr/bin/env python
"""Validate dpwa metrics JSONL files against the frozen record schemas.

The JSONL streams are the repo's observability contract: every
downstream consumer (tools/health_report.py, tools/trace_report.py,
jq one-liners, soak-run dashboards) reads them by field name, and the
planes keep old records **byte-identical** when a new plane is off —
so a field renamed, retyped, or silently added is a cross-PR
regression even when every unit test passes.  This checker pins the
schemas:

- ``record: "health"`` — the scoreboard snapshot columns, plus the
  optional membership / trust / flowctl / wire / obs column groups
  (each group is all-or-nothing: a record with ``trust`` but without
  ``trust_verdict`` is malformed);
- ``record: "trace"``, ``kind: "round" | "serve"`` — the obs plane's
  round/serve spans (docs/observability.md);
- ``record: "event"`` — control-plane events: ``step``/``t``/``event``
  are pinned, the ``event`` kind must be registered in
  :data:`EVENT_KINDS`, evidence fields are free-form by design (each
  event kind carries its own);
- ``record: "alert"`` / ``record: "incident"`` — the incident plane's
  detector alerts and correlated incident lifecycle records
  (docs/incidents.md), both closed-world;
- ``record: "flight"``, ``kind: "meta" | "round"`` — the flight
  recorder's post-mortem dump header and per-round ring entries;
- ``record: "fleet"``, ``kind: "churn" | "round" | "episode"`` — the
  churn orchestrator's stream (docs/fleet.md): churn records are the
  deterministic bit-identity anchor (round counters and peer ids
  only), round records add measured fields, episode records the run
  summary ``tools/fleet_report.py`` digests — all closed-world;
- ``record: "island"`` — per-island convergence/leadership rows from
  the hierarchical planes (docs/hierarchy.md), closed-world;
- ``record: "run"`` — the training harness's run envelope
  (docs/training.md): one ``status: "start"`` record pinning the leg's
  shape (model, d, peers, seed) and one terminal ``"done"``/
  ``"crashed"`` record carrying the outcome, closed-world;
- ``record: "loss"`` — the training harness's per-step loss stream
  (``tools/run_report.py`` joins these against the incident plane),
  closed-world;
- ``record: "tune"`` — the self-tuning wire's per-link ladder
  decisions (docs/tune.md): escalate/backoff/shed_on/shed_off rows,
  the determinism anchor for seeded controller reruns — closed-world;
- records with no ``record`` key — per-step exchange/training records
  (``MetricsLogger.log`` / ``log_exchange``): ``step`` and ``t`` are
  pinned, the rest is adapter-defined.

Any other ``record`` kind is an error — a new emitter must register
its schema here (tools/lint_emitters.py statically enforces the same
registry over the source tree; tests/test_static_checks.py wires both
into tier-1).

Unknown fields in a pinned schema, missing required fields, and
mistyped pinned fields are errors; the exit code is the error count
(0 = clean), so the check can run in tier-1 and in soak harnesses.

Usage::

    python tools/schema_check.py metrics.jsonl [more.jsonl ...]
    python tools/schema_check.py --json metrics.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

_NUM = (int, float)

# Pinned field -> allowed types.  ``list`` columns are parallel arrays
# keyed by the record's ``peer`` column.
_HEALTH_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "me": (int,),
    "round": (int,),
    "peer": (list,),
    "peer_state": (list,),
    "suspicion": (list,),
    "quarantined_rounds": (list,),
    "quarantines": (list,),
    "attempts": (list,),
    "failures": (list,),
    "probe_attempts": (list,),
    "last_outcome": (list,),
}

# Optional column GROUPS: a plane contributes all of its columns or
# none of them (that is what keeps plane-off records byte-identical).
_HEALTH_GROUPS: Dict[str, Dict[str, tuple]] = {
    "membership": {
        "incarnation": (list,),
        "own_incarnation": (int,),
        "component": (list,),
        "component_id": _NUM + (str, type(None)),
        "partition_state": (str,),
    },
    "trust": {
        "trust": (list,),
        "trust_verdict": (list,),
        "trust_damped": (list,),
        "trust_rejected": (list,),
    },
    "flowctl": {
        "deadline_ms": (list,),
        "hedges": (list,),
        "hedge_wins": (list,),
        "busy": (list,),
        "slow": (list,),
        "hedge_rate": _NUM,
        "shed_total": (int,),
    },
    "wire": {
        "wire_codec": (str,),
        "wire_bytes": (int,),
        "compression_ratio": _NUM,
    },
    # Zero-copy frame path (its own group, not folded into "wire":
    # wire records written before the ring existed stay valid).
    "zerocopy": {
        "copies_per_frame": _NUM,
        "ring_occupancy": _NUM,
    },
    "overlap": {
        "overlap_occupancy": _NUM,
        "overlap_hidden_frac": _NUM,
        "overlap_prefetched": (int,),
        "overlap_straddled": (int,),
    },
    # Sharded wire (shard.k > 1).
    "shard": {
        "shard_k": (int,),
        "shard_coverage": _NUM,
    },
    # Bounded partial views (docs/membership.md; present exactly when
    # membership.view is on): view sizes, tracked residency vs the
    # state cap, per-frame digest footprint, evictions by cause.
    "view": {
        "view_active": (int,),
        "view_passive": (int,),
        "view_tracked": (int,),
        "view_capped": (int,),
        "view_digest_entries": (int,),
        "view_digest_bytes": (int,),
        "view_evicted_dead": (int,),
        "view_evicted_cap": (int,),
        "view_promotions": (int,),
        "view_shuffles": (int,),
    },
    # Device merge engine (docs/device.md; absent until a device-
    # resident exchange has served a round).
    "device": {
        "device_rounds": (int,),
        "jit_cache_hits": (int,),
        "jit_cache_misses": (int,),
        "device_dispatches_per_round": _NUM,
        "h2d_zero_copy_frac": _NUM,
        "fold_frames": (int,),
    },
    "obs": {
        "disagreement_rms": _NUM + (type(None),),
        "disagreement_rel": _NUM + (type(None),),
        "sketch_peers": (int,),
    },
    "reactor": {
        "reactor_loop_lag_ms": _NUM,
        "reactor_ready_depth": (int,),
        "reactor_open": (int,),
        "reactor_evicted": (int,),
        "reactor_busy_shed": (int,),
    },
    # Barrier-free async round loop (docs/async.md; present exactly
    # when protocol.async_rounds drives the transport).
    # ``async_staleness_hist`` is a lag histogram (buckets 0..
    # max_staleness + overflow), not a per-peer column — exempted from
    # the parallel-array check below, like ``component``.
    "async": {
        "async_rounds": (int,),
        "async_merges": (int,),
        "async_stale_drops": (int,),
        "async_dup_drops": (int,),
        "async_shed": (int,),
        "async_fold_frames": (int,),
        "async_staleness_hist": (list,),
        "async_peer_merges": (list,),
        "async_peer_stale": (list,),
        "async_peer_pending": (list,),
        "async_peer_lag": (list,),
    },
    # Self-tuning wire (docs/tune.md; present exactly when tune.enabled
    # drives the transport): per-link EFFECTIVE rung/codec columns and
    # the ladder's lifetime traffic counters.  ``tune_dwell_violations``
    # is the hysteresis invariant — always 0 in a healthy run.
    "tune": {
        "tune_rung": (list,),
        "tune_codec": (list,),
        "tune_shed": (list,),
        "tune_escalations": (int,),
        "tune_backoffs": (int,),
        "tune_sheds": (int,),
        "tune_dwell_violations": (int,),
    },
}

_TRACE_ROUND_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "kind": (str,),
    "me": (int,),
    "stages": (dict,),
}
_TRACE_ROUND_OPTIONAL: Dict[str, tuple] = {
    "trace_id": (str,),
    "remote_trace_id": (str,),
    "partner": (int,),
    "sched_partner": (int,),
    "remapped": (bool,),
    "outcome": (str,),
    "codec": (str,),
    "nbytes": (int,),
    "alpha": _NUM,
    "hedged": (bool,),
    "prefetched": (bool,),
    "straddled": (bool,),
    "disagreement_rms": _NUM,
    "disagreement_rel": _NUM,
}

_TRACE_SERVE_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "kind": (str,),
    "me": (int,),
    "trace_id": (str,),
    "nbytes": (int,),
    "dur_s": _NUM,
}

_EVENT_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "event": (str,),
}

_ALERT_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "kind": (str,),
    "severity": (str,),
    "plane": (str,),
    "value": _NUM,
    "threshold": _NUM,
}
_ALERT_OPTIONAL: Dict[str, tuple] = {
    "peer": (int,),
    "peers": (list,),
    "window": (int,),
}

_INCIDENT_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
    "record": (str,),
    "id": (str,),
    "status": (str,),
    "kind": (str,),
    "severity": (str,),
    "peers": (list,),
    "alerts": (int,),
    "opened_step": (int,),
    "me": (int,),
}
_INCIDENT_OPTIONAL: Dict[str, tuple] = {
    "resolved_step": (int,),
}

_FLIGHT_META_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "kind": (str,),
    "me": (int,),
    "step": (int,),
    "t": _NUM,
    "reason": (str,),
    "rounds": (int,),
    "dumps": (int,),
}

_FLIGHT_ROUND_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "kind": (str,),
    "me": (int,),
    "step": (int,),
    "t": _NUM,
}
_FLIGHT_ROUND_OPTIONAL: Dict[str, tuple] = {
    "partner": (int,),
    "sched_partner": (int,),
    "remapped": (bool,),
    "outcome": (str,),
    "codec": (str,),
    "trust": (dict,),
    "latency_s": _NUM,
    "nbytes": (int,),
    "rel_rms": _NUM,
    "wall_s": _NUM,
    "partition_state": (str,),
    "events": (list,),
    "alerts": (list,),
}

# Fleet records carry ``round`` (gossip round), never ``t``: the churn
# stream is the orchestrator's BIT-IDENTITY anchor (two runs of one
# seed must produce byte-identical churn records), so wall time never
# enters it.  Measured fields live on round/episode records only.
_FLEET_CHURN_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "kind": (str,),
    "round": (int,),
    "leaves": (list,),
    "joins": (list,),
    "cohort": (list,),
    "restart": (list,),
    "chaos": (list,),
    "live": (int,),
    "evicted": (list,),
}
# Hierarchical fleets only (docs/hierarchy.md): the island-granular
# churn families.  All-or-nothing in practice (the orchestrator adds
# the whole group when a topology is configured), optional here so
# flat churn records stay byte-identical.
_FLEET_CHURN_OPTIONAL: Dict[str, tuple] = {
    "island_leaves": (list,),
    "island_joins": (list,),
    "churned_islands": (list,),
    "leader_restarts": (list,),
}

_FLEET_ROUND_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "kind": (str,),
    "round": (int,),
    "live": (int,),
    "exchanges": (int,),
    "failures": (int,),
    "outcomes": (dict,),
    "rel_rms": _NUM,
    "wall_s": _NUM,
    "digest_bytes": (int,),
    "evicted": (int,),
    "alerts": (list,),
}

_FLEET_EPISODE_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "kind": (str,),
    "rounds": (int,),
    "n_peers": (int,),
    "seed": (int,),
    "final_live": (int,),
    "final_rel_rms": _NUM,
    "outcomes": (dict,),
    "max_digest_bytes": (int,),
    "max_wall_s": _NUM,
    "evicted": (list,),
    "leave_convergence_rounds": (list,),
    "join_convergence_rounds": (list,),
    "unresolved_leaves": (list,),
    "unresolved_joins": (list,),
    "alerts": (dict,),
    "incidents_opened": (int,),
}
_FLEET_EPISODE_OPTIONAL: Dict[str, tuple] = {
    "islands": (int,),
    "leader_terms": (dict,),
    # membership.view-only (docs/membership.md): worst-case per-node
    # residency, present iff the partial-view plane is enabled.
    "view_max_resident_bytes": (int,),
    "view_max_tracked": (int,),
    "view_max_digest_entries": (int,),
}

# Per-island convergence records (docs/hierarchy.md): one per island
# per round from the hier engine / orchestrator.  ``rel_rms`` is the
# INTRA-island disagreement; ``term`` is the island's leadership term.
_ISLAND_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "round": (int,),
    "island": (str,),
    "term": (int,),
    "live": (int,),
    "rel_rms": _NUM,
}
_ISLAND_OPTIONAL: Dict[str, tuple] = {
    "leader": (int,),
    "wide_frames": (int,),
    "t": _NUM,
}

_EXCHANGE_REQUIRED: Dict[str, tuple] = {
    "step": (int,),
    "t": _NUM,
}

# Training-harness run envelope (dpwa_tpu/run, docs/training.md): a
# ``status: "start"`` record opens every per-node stream with the leg's
# full shape, and exactly one terminal record (``done`` or ``crashed``)
# carries the outcome fields run_report/train_gate consume.
_RUN_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "step": (int,),
    "t": _NUM,
    "me": (int,),
    "leg": (str,),
    "status": (str,),
    "peers": (int,),
    "seed": (int,),
}
_RUN_OPTIONAL: Dict[str, tuple] = {
    "model": (str,),
    "dataset": (str,),
    "d": (int,),
    "steps": (int,),
    "batch_size": (int,),
    "lr": _NUM,
    "target_loss": _NUM,
    "async_rounds": (bool,),
    "rx_server": (str,),
    "final_loss": _NUM,
    "best_loss": _NUM,
    "time_to_target_s": _NUM + (type(None),),
    "steps_to_target": (int, type(None)),
    "wall_s": _NUM,
    "checkpoint_restored_step": (int,),
}

# Training-harness loss stream: the per-step record run_report joins
# against the incident plane.  ``loss`` is the node's own minibatch
# loss; merge metadata (alpha/partner/outcome) rides along so the dent
# analysis can see WHICH merges moved the curve.
_LOSS_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "step": (int,),
    "t": _NUM,
    "me": (int,),
    "loss": _NUM,
}
_LOSS_OPTIONAL: Dict[str, tuple] = {
    "epoch": (int,),
    "alpha": _NUM,
    "partner": (int, type(None)),
    "outcome": (str, type(None)),
    "test_loss": _NUM,
    "test_acc": _NUM,
}

# Self-tuning wire ladder decisions (docs/tune.md): one row per
# escalate/backoff/shed transition, written immediately like events.
# CLOSED: the decision log is the controller determinism test's
# bit-identity fixture — a free-form field would let noise in.
_TUNE_REQUIRED: Dict[str, tuple] = {
    "record": (str,),
    "step": (int,),
    "t": _NUM,
    "link": (int,),
    "round": (int,),
    "action": (str,),
    "rung": (int,),
    "prev_rung": (int,),
    "codec": (str,),
    "reason": (str,),
    "dwell": (int,),
}
_TUNE_ACTIONS = frozenset(
    {"escalate", "backoff", "shed_on", "shed_off"}
)

# The registry tools/lint_emitters.py checks emit sites against: every
# ``record`` kind and every ``event`` kind the tree may write.  A new
# emitter extends these IN THE SAME CHANGE that adds its schema above.
RECORD_KINDS = frozenset(
    {
        "health", "trace", "event", "alert", "incident", "flight",
        "fleet", "island", "run", "loss", "tune",
    }
)
EVENT_KINDS = frozenset(
    {
        # recovery / bootstrap (PR 2)
        "bootstrap", "bootstrap_failed", "rollback", "resync",
        "resync_advised",
        # supervisor lifecycle (tools/supervisor.py)
        "spawn", "crashed", "exited", "gave_up", "restart_scheduled",
        "unhealthy",
        # membership (PR 3)
        "refutation", "peer_refuted", "component_changed",
        "partition_entered", "partition_healed",
        "partition_reconciled", "partition_reconcile_failed",
        "partition_reconcile_rejected",
        # trust (PR 4)
        "trust_amnesty", "trust_clock_reset", "trust_collapsed",
        "trust_recovered",
        # churn-hardened membership eviction (PR 11, docs/fleet.md)
        "peer_dead", "peer_rejoined",
        # hierarchical gossip leadership (PR 12, docs/hierarchy.md)
        "leader_elected", "leader_failover",
        # bounded partial views (PR 18, docs/membership.md): LRU cap
        # eviction is untracked-not-dead, so it gets its own kind.
        "peers_capped",
    }
)


def _check_fields(
    rec: dict,
    required: Dict[str, tuple],
    optional: Optional[Dict[str, tuple]] = None,
    closed: bool = False,
) -> List[str]:
    errs: List[str] = []
    known = dict(required)
    if optional:
        known.update(optional)
    for field, types in required.items():
        if field not in rec:
            errs.append(f"missing required field {field!r}")
        elif not isinstance(rec[field], types):
            errs.append(
                f"field {field!r} has type "
                f"{type(rec[field]).__name__}, expected "
                f"{'/'.join(t.__name__ for t in types)}"
            )
    if optional:
        for field, types in optional.items():
            if field in rec and not isinstance(rec[field], types):
                errs.append(
                    f"field {field!r} has type "
                    f"{type(rec[field]).__name__}, expected "
                    f"{'/'.join(t.__name__ for t in types)}"
                )
    if closed:
        for field in rec:
            if field not in known:
                errs.append(f"unknown field {field!r}")
    return errs


def check_record(rec: dict) -> List[str]:
    """Errors for one parsed JSONL record (empty = valid)."""
    kind = rec.get("record")
    if kind == "health":
        errs = _check_fields(rec, _HEALTH_REQUIRED)
        # Group completeness + closed-world over required ∪ groups.
        known = dict(_HEALTH_REQUIRED)
        for group, fields in _HEALTH_GROUPS.items():
            known.update(fields)
            present = [f for f in fields if f in rec]
            if present and len(present) != len(fields):
                missing = sorted(set(fields) - set(present))
                errs.append(
                    f"partial {group!r} column group: missing {missing}"
                )
            for f in present:
                if not isinstance(rec[f], fields[f]):
                    errs.append(
                        f"field {f!r} has type "
                        f"{type(rec[f]).__name__}, expected "
                        f"{'/'.join(t.__name__ for t in fields[f])}"
                    )
        for field in rec:
            if field not in known:
                errs.append(f"unknown field {field!r}")
        # Parallel-array discipline: every list column matches peer.
        # (``component`` is the membership member list and
        # ``async_staleness_hist`` a lag histogram, not per-peer
        # columns; ``peer`` is the key column itself.)
        peers = rec.get("peer")
        if isinstance(peers, list):
            for f, v in rec.items():
                if f in ("peer", "component", "async_staleness_hist"):
                    continue
                if isinstance(v, list) and len(v) != len(peers):
                    errs.append(
                        f"column {f!r} has {len(v)} entries for "
                        f"{len(peers)} peers"
                    )
        return errs
    if kind == "trace":
        tkind = rec.get("kind")
        if tkind == "round":
            return _check_fields(
                rec, _TRACE_ROUND_REQUIRED, _TRACE_ROUND_OPTIONAL,
                closed=True,
            )
        if tkind == "serve":
            return _check_fields(rec, _TRACE_SERVE_REQUIRED, closed=True)
        return [f"unknown trace kind {tkind!r}"]
    if kind == "event":
        # Evidence fields are free-form by design; only the envelope is
        # pinned — but the kind itself must be registered.
        errs = _check_fields(rec, _EVENT_REQUIRED)
        ev = rec.get("event")
        if isinstance(ev, str) and ev not in EVENT_KINDS:
            errs.append(f"unregistered event kind {ev!r}")
        return errs
    if kind == "alert":
        return _check_fields(
            rec, _ALERT_REQUIRED, _ALERT_OPTIONAL, closed=True
        )
    if kind == "incident":
        return _check_fields(
            rec, _INCIDENT_REQUIRED, _INCIDENT_OPTIONAL, closed=True
        )
    if kind == "flight":
        fkind = rec.get("kind")
        if fkind == "meta":
            return _check_fields(rec, _FLIGHT_META_REQUIRED, closed=True)
        if fkind == "round":
            return _check_fields(
                rec, _FLIGHT_ROUND_REQUIRED, _FLIGHT_ROUND_OPTIONAL,
                closed=True,
            )
        return [f"unknown flight kind {fkind!r}"]
    if kind == "fleet":
        fkind = rec.get("kind")
        if fkind == "churn":
            return _check_fields(
                rec, _FLEET_CHURN_REQUIRED, _FLEET_CHURN_OPTIONAL,
                closed=True,
            )
        if fkind == "round":
            return _check_fields(rec, _FLEET_ROUND_REQUIRED, closed=True)
        if fkind == "episode":
            return _check_fields(
                rec, _FLEET_EPISODE_REQUIRED, _FLEET_EPISODE_OPTIONAL,
                closed=True,
            )
        return [f"unknown fleet kind {fkind!r}"]
    if kind == "island":
        return _check_fields(
            rec, _ISLAND_REQUIRED, _ISLAND_OPTIONAL, closed=True
        )
    if kind == "run":
        errs = _check_fields(rec, _RUN_REQUIRED, _RUN_OPTIONAL, closed=True)
        status = rec.get("status")
        if isinstance(status, str) and status not in (
            "start", "done", "crashed"
        ):
            errs.append(f"unknown run status {status!r}")
        return errs
    if kind == "loss":
        return _check_fields(
            rec, _LOSS_REQUIRED, _LOSS_OPTIONAL, closed=True
        )
    if kind == "tune":
        errs = _check_fields(rec, _TUNE_REQUIRED, closed=True)
        action = rec.get("action")
        if isinstance(action, str) and action not in _TUNE_ACTIONS:
            errs.append(f"unknown tune action {action!r}")
        return errs
    if kind is None:
        return _check_fields(rec, _EXCHANGE_REQUIRED)
    return [f"unknown record kind {kind!r}"]


def check_file(path: str) -> Tuple[int, List[dict]]:
    """(records_checked, error_entries) for one JSONL file."""
    n = 0
    errors: List[dict] = []
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(
                    {"file": path, "line": lineno,
                     "errors": [f"unparseable JSON: {e}"]}
                )
                continue
            if not isinstance(rec, dict):
                errors.append(
                    {"file": path, "line": lineno,
                     "errors": ["record is not a JSON object"]}
                )
                continue
            n += 1
            errs = check_record(rec)
            if errs:
                errors.append(
                    {"file": path, "line": lineno, "errors": errs}
                )
    return n, errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Validate dpwa metrics JSONL against the frozen "
        "record schemas."
    )
    ap.add_argument("paths", nargs="+", help="JSONL files to check")
    ap.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    args = ap.parse_args(argv)
    total = 0
    all_errors: List[dict] = []
    for path in args.paths:
        n, errors = check_file(path)
        total += n
        all_errors.extend(errors)
    if args.json:
        json.dump(
            {
                "records": total,
                "error_count": len(all_errors),
                "errors": all_errors,
            },
            sys.stdout,
            indent=2,
        )
        print()
    else:
        for entry in all_errors:
            for e in entry["errors"]:
                print(f"{entry['file']}:{entry['line']}: {e}")
        status = "FAIL" if all_errors else "OK"
        print(
            f"{status}: {total} records checked, "
            f"{len(all_errors)} bad record(s)"
        )
    return min(len(all_errors), 125)


if __name__ == "__main__":
    sys.exit(main())
