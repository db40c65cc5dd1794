"""The four chaos-certified acceptance legs + the LoRA small-frame leg.

Each leg drives real training through the real stack and renders a
**verdict dict** whose booleans are the acceptance criteria
(docs/training.md):

- :func:`clean_leg` — time-to-loss within tolerance of single-process
  SGD at equal total optimizer steps;
- :func:`byzantine_leg` — a chaos byzantine window dents the observer's
  curve boundedly, trust quarantines the offender within K rounds, the
  incident plane brackets the dent, and the curve re-converges;
- :func:`crash_leg` — a worker SIGKILLs mid-training; the supervisor
  restarts it, it restores its newest valid checkpoint, refines over
  the STATE wire, and its loss rejoins the cohort;
- :func:`straggler_leg` — a trickle-shaped peer must not throttle the
  honest peers' time-to-loss when async rounds are on;
- :func:`lora_leg` — the d≈100K adapter-only exchange (small-frame
  regime) learns through the zero-copy ring.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Any, Dict, List, Optional

from dpwa_tpu.config import make_local_config
from dpwa_tpu.run.harness import run_single, run_training
from dpwa_tpu.run.report import build_report
from dpwa_tpu.run.task import make_task

# Per-task training hyperparameters that reach the target in tens of
# steps on CPU (calibrated; the legs' runtime budget is tier-1's).
TASK_DEFAULTS = {
    "blobs": {"steps": 48, "batch_size": 32, "lr": 0.5, "target_loss": 0.4},
    "digits": {"steps": 80, "batch_size": 32, "lr": 0.1, "target_loss": 0.7},
    "lora": {"steps": 40, "batch_size": 32, "lr": 0.3, "target_loss": 1.5},
}


@dataclasses.dataclass
class LegResult:
    """One leg's outcome: ``ok`` is the AND of every acceptance bool in
    ``verdict``; ``summary`` is the raw harness output; ``report`` the
    loss/incident join."""

    leg: str
    ok: bool
    verdict: Dict[str, Any]
    summary: Dict[str, Any]
    report: Dict[str, Any]
    workdir: str

    def to_record(self) -> dict:
        """The compact form: leg, ok, verdict."""
        return {"leg": self.leg, "ok": self.ok, "verdict": self.verdict}


def _run_block(task_name: str, **overrides) -> dict:
    run = dict(TASK_DEFAULTS[task_name])
    for key in sorted(overrides):
        if overrides[key] is not None:
            run[key] = overrides[key]
    return run


def _median(values: List[float]) -> Optional[float]:
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    mid = len(vals) // 2
    if len(vals) % 2:
        return float(vals[mid])
    return (vals[mid - 1] + vals[mid]) / 2.0


def _verdict_ok(verdict: dict) -> bool:
    return all(
        bool(verdict[k]) for k in sorted(verdict) if k.endswith("_ok")
    )


# ---------------------------------------------------------------------------
# Clean leg
# ---------------------------------------------------------------------------


def clean_leg(
    workdir: str,
    *,
    n_peers: int = 8,
    task: str = "blobs",
    seed: int = 11,
    base_port: int = 46600,
    steps: Optional[int] = None,
    target_loss: Optional[float] = None,
    steps_tol: float = 1.6,
    rx_server: str = "threaded",
) -> LegResult:
    """Gossip time-to-loss vs single-process SGD at equal total steps.

    Equal TOTAL OPTIMIZER STEPS per replica: both arms take the same
    number of SGD steps; the gossip arm additionally pays a full
    exchange (publish → fetch → guard → trust → merge) each step.  The
    leg passes when the gossip cohort's median steps-to-target is
    within ``steps_tol`` of the single run's — pairwise averaging must
    not wreck the curve."""
    run = _run_block(task, steps=steps, target_loss=target_loss)
    task_obj = make_task(task, seed=seed)
    gossip_dir = os.path.join(workdir, "gossip")
    single_dir = os.path.join(workdir, "single")
    config = make_local_config(
        n_peers,
        seed=seed,
        base_port=base_port,
        run=run,
        rx_server=rx_server,
        obs=dict(
            incidents=True,
            incident_path=os.path.join(gossip_dir, "incidents-{me}.jsonl"),
        ),
    )
    summary = run_training(config, task_obj, gossip_dir, leg="clean")
    # The control arm reuses the same config (run block + seed); with
    # gossip off no transport is built, so the node list is inert.
    single = run_single(config, task_obj, single_dir)
    report = build_report(gossip_dir)
    gossip_stt = _median(
        [n["steps_to_target"] for n in summary["nodes"]]
    )
    single_stt = single["nodes"][0]["steps_to_target"]
    incidents = sum(
        len(n["incident_clusters"]) for n in report["nodes"].values()
    )
    verdict = {
        "gossip_steps_to_target": gossip_stt,
        "single_steps_to_target": single_stt,
        "steps_tol": steps_tol,
        "gossip_final_loss": _median(
            [n["final_loss"] for n in summary["nodes"]]
        ),
        "single_final_loss": single["nodes"][0]["final_loss"],
        "incident_clusters": incidents,
        "converged_ok": gossip_stt is not None,
        "single_converged_ok": single_stt is not None,
        "time_to_quality_ok": (
            gossip_stt is not None
            and single_stt is not None
            and gossip_stt <= steps_tol * single_stt
        ),
        "quiet_incidents_ok": incidents == 0,
    }
    summary["single"] = single
    return LegResult(
        leg="clean",
        ok=_verdict_ok(verdict),
        verdict=verdict,
        summary=summary,
        report=report,
        workdir=workdir,
    )


# ---------------------------------------------------------------------------
# Byzantine leg
# ---------------------------------------------------------------------------


def byzantine_leg(
    workdir: str,
    *,
    n_peers: int = 4,
    task: str = "blobs",
    seed: int = 23,
    base_port: int = 46700,
    attacker: int = 1,
    attack_from: Optional[int] = None,
    kind: str = "sign",
    quarantine_k: int = 8,
    steps: Optional[int] = None,
) -> LegResult:
    """A byzantine window mid-run: bounded dent, quarantine within K
    rounds, incident plane brackets the dent, curve re-converges."""
    run = _run_block(task, steps=steps)
    if attack_from is None:
        attack_from = run["steps"] // 3
    task_obj = make_task(task, seed=seed)
    config = make_local_config(
        n_peers,
        seed=seed,
        base_port=base_port,
        run=run,
        timeout_ms=800,
        trust=dict(window=16, min_window=4),
        health=dict(jitter_rounds=1, quarantine_base_rounds=4),
        chaos=dict(
            enabled=True,
            seed=seed + 17,
            byzantine_peers=(attacker,),
            byzantine_start_round=attack_from,
            **{f"byzantine_{kind}_probability": 1.0},
        ),
        obs=dict(
            incidents=True,
            incident_path=os.path.join(workdir, "incidents-{me}.jsonl"),
        ),
    )
    summary = run_training(config, task_obj, workdir, leg="byzantine")
    report = build_report(workdir)
    honest = [i for i in range(n_peers) if i != attacker]
    # Quarantine evidence from the final health snapshots: every honest
    # node quarantined the attacker (by its own screening OR by adopting
    # the quarantine epidemically — a node the ring never pairs with the
    # attacker still learns to avoid it), and the nodes that DID screen
    # it personally collapsed its trust.
    quarantined = []
    screened = 0
    for i in honest:
        peer = summary["nodes"][i]["health"]["peers"][attacker]
        quarantined.append(peer.get("quarantines", 0) >= 1)
        if peer.get("trust_rejected", 0) >= 1:
            screened += 1
            quarantined[-1] = (
                quarantined[-1] and peer.get("trust", 1.0) < 0.5
            )
    # Time-to-quarantine from the observers' merge columns: the first
    # ``untrusted`` outcome any honest node logged.
    first_untrusted: Optional[int] = None
    for i in honest:
        sig = report["nodes"][i]["first_signal"]
        if sig is not None and sig["plane"] == "trust":
            if first_untrusted is None or sig["step"] < first_untrusted:
                first_untrusted = sig["step"]
    # The observer's dent and its incident bracket.
    obs_node = report["nodes"][0]
    dent = obs_node["dent"]
    clusters = obs_node["incident_clusters"]
    bracketing = [
        c for c, br in zip(clusters, obs_node["bracketed"]) if br
    ]
    final = _median([summary["nodes"][i]["final_loss"] for i in honest])
    target = run["target_loss"]
    verdict = {
        "attacker": attacker,
        "attack_from": attack_from,
        "first_untrusted_step": first_untrusted,
        "quarantine_k": quarantine_k,
        "dent": dent,
        "incident_clusters": len(clusters),
        "bracketing_clusters": len(bracketing),
        "honest_final_loss": final,
        "screening_nodes": screened,
        "quarantined_ok": all(quarantined)
        and len(quarantined) > 0
        and screened >= 2,
        # The publish clock leads the step by one, so the first lying
        # frame can land at step attack_from - 1.
        "quarantine_time_ok": (
            first_untrusted is not None
            and attack_from - 1
            <= first_untrusted
            <= attack_from + quarantine_k
        ),
        "dent_bounded_ok": dent is None or dent["excursion"] <= 20.0,
        "reconverged_ok": (
            final is not None
            and final <= max(2.0 * target, target + 0.2)
            and (dent is None or dent["recovered"])
        ),
        "incident_bracket_ok": (
            dent is None or len(bracketing) >= 1
        ),
        "single_cluster_ok": len(clusters) <= 1,
    }
    return LegResult(
        leg="byzantine",
        ok=_verdict_ok(verdict),
        verdict=verdict,
        summary=summary,
        report=report,
        workdir=workdir,
    )


# ---------------------------------------------------------------------------
# Crash leg (subprocess workers under the restart supervisor)
# ---------------------------------------------------------------------------


def crash_leg(
    workdir: str,
    *,
    n_peers: int = 4,
    task: str = "blobs",
    seed: int = 31,
    base_port: int = 46800,
    victim: int = 1,
    crash_at: int = 12,
    checkpoint_every: int = 5,
    steps: int = 90,
    step_sleep_s: float = 0.08,
    timeout_s: float = 120.0,
    rejoin_loss_factor: float = 3.0,
) -> LegResult:
    """SIGKILL a worker mid-training; prove checkpoint restore + STATE
    rejoin land its loss back in the cohort.

    Free-running subprocess workers (one per node, the real deployment
    shape) under ``tools/supervisor.py``.  The victim kills itself —
    SIGKILL, nothing flushes — at ``crash_at``; the supervisor restarts
    it with ``DPWA_BOOTSTRAP=1``."""
    from tools.supervisor import Supervisor, WorkerSpec

    os.makedirs(workdir, exist_ok=True)
    run = _run_block(task, steps=steps)
    run["checkpoint_every"] = checkpoint_every
    run["checkpoint_dir"] = os.path.join(workdir, "ckpt")
    spec = {
        "n": n_peers,
        "seed": seed,
        "base_port": base_port,
        "task": task,
        "leg": "crash",
        "workdir": workdir,
        "run": run,
        "protocol": {"timeout_ms": 800},
        "health": {"jitter_rounds": 1},
        "obs": {
            "incidents": True,
            "incident_path": os.path.join(workdir, "incidents-{me}.jsonl"),
        },
        "crash_at_step": {str(victim): crash_at},
        "step_sleep_s": step_sleep_s,
    }
    spec_path = os.path.join(workdir, "run.json")
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f, indent=2)
    workers = [
        WorkerSpec(
            name=f"node{i}",
            argv=[
                sys.executable, "-m", "dpwa_tpu.run.worker",
                "--spec", spec_path, "--index", str(i),
            ],
        )
        for i in range(n_peers)
    ]
    sup = Supervisor(
        workers, max_restarts=3, backoff_base_s=0.2, backoff_max_s=2.0
    )
    sup.start()
    final = sup.run(timeout_s=timeout_s)
    report = build_report(workdir)
    victim_node = report["nodes"].get(victim, {})
    honest = [i for i in sorted(report["nodes"]) if i != victim]
    honest_final = _median(
        [report["nodes"][i]["final_ewma"] for i in honest]
    )
    victim_final = victim_node.get("final_ewma")
    victim_done = victim_node.get("done")
    crash_events = [
        e for e in sup.events if e["event"] == "crashed"
    ]
    verdict = {
        "supervisor": final,
        "crash_events": len(crash_events),
        "victim_crashes_logged": victim_node.get("crashes", 0),
        "victim_restored_step": victim_node.get("restored_step", 0),
        "victim_final_ewma": victim_final,
        "honest_final_ewma": honest_final,
        "crashed_ok": len(crash_events) >= 1,
        "restarted_ok": final["restarts"].get(f"node{victim}", 0) >= 1
        and final["gave_up"] == 0,
        "checkpoint_restored_ok": (
            victim_node.get("restored_step", 0) >= checkpoint_every
        ),
        "completed_ok": victim_done is not None,
        "rejoined_ok": (
            victim_final is not None
            and honest_final is not None
            and victim_final
            <= max(rejoin_loss_factor * honest_final, honest_final + 0.3)
        ),
    }
    return LegResult(
        leg="crash",
        ok=_verdict_ok(verdict),
        verdict=verdict,
        summary={"supervisor_events": sup.events, "spec": spec},
        report=report,
        workdir=workdir,
    )


# ---------------------------------------------------------------------------
# Straggler leg
# ---------------------------------------------------------------------------


def straggler_leg(
    workdir: str,
    *,
    n_peers: int = 4,
    task: str = "blobs",
    seed: int = 41,
    base_port: int = 46900,
    steps: Optional[int] = None,
    trickle_bytes_per_s: float = 512.0,
    wall_tol: float = 2.0,
    steps_tol: float = 1.5,
) -> LegResult:
    """A trickle-shaped peer must not throttle honest time-to-loss with
    async rounds on.

    Two seeded runs, identical but for chaos: a baseline (async on, no
    shaping) and the straggler run (peer ``n-1``'s SERVING trickles for
    the whole run).  Honest nodes' own wall time and steps-to-target
    must stay within tolerance — barrier-free rounds mean a slow peer
    costs its own frames, not the cohort's round rate."""
    run = _run_block(task, steps=steps)
    task_obj = make_task(task, seed=seed)
    async_block = {"enabled": True, "max_staleness": 6}
    base_dir = os.path.join(workdir, "baseline")
    slow_dir = os.path.join(workdir, "straggler")
    straggler = n_peers - 1
    base_cfg = make_local_config(
        n_peers,
        seed=seed,
        base_port=base_port,
        run=run,
        timeout_ms=800,
        async_rounds=async_block,
    )
    baseline = run_training(base_cfg, task_obj, base_dir, leg="straggler")
    slow_cfg = make_local_config(
        n_peers,
        seed=seed,
        base_port=base_port + n_peers,
        run=run,
        timeout_ms=800,
        async_rounds=async_block,
        chaos=dict(
            enabled=True,
            seed=seed + 5,
            trickle_windows=((straggler, 0, run["steps"]),),
            trickle_bytes_per_s=trickle_bytes_per_s,
        ),
    )
    shaped = run_training(slow_cfg, task_obj, slow_dir, leg="straggler")
    honest = [i for i in range(n_peers) if i != straggler]
    base_wall = _median(
        [baseline["nodes"][i]["wall_s"] for i in honest]
    )
    slow_wall = _median([shaped["nodes"][i]["wall_s"] for i in honest])
    base_stt = _median(
        [baseline["nodes"][i]["steps_to_target"] for i in honest]
    )
    slow_stt = _median(
        [shaped["nodes"][i]["steps_to_target"] for i in honest]
    )
    verdict = {
        "straggler": straggler,
        "honest_wall_s_baseline": base_wall,
        "honest_wall_s_straggler": slow_wall,
        "honest_steps_to_target_baseline": base_stt,
        "honest_steps_to_target_straggler": slow_stt,
        "wall_tol": wall_tol,
        "steps_tol": steps_tol,
        "converged_ok": slow_stt is not None and base_stt is not None,
        "unthrottled_wall_ok": (
            base_wall is not None
            and slow_wall is not None
            and slow_wall <= wall_tol * max(base_wall, 0.05)
        ),
        "time_to_quality_ok": (
            base_stt is not None
            and slow_stt is not None
            and slow_stt <= steps_tol * base_stt
        ),
    }
    return LegResult(
        leg="straggler",
        ok=_verdict_ok(verdict),
        verdict=verdict,
        summary={"baseline": baseline, "straggler": shaped},
        report=build_report(slow_dir),
        workdir=workdir,
    )


# ---------------------------------------------------------------------------
# LoRA small-frame leg
# ---------------------------------------------------------------------------


def lora_leg(
    workdir: str,
    *,
    n_peers: int = 4,
    seed: int = 53,
    base_port: int = 47000,
    steps: Optional[int] = None,
    rx_server: str = "threaded",
) -> LegResult:
    """Adapter-only exchange at d≈100K (~392 KiB frames) through the
    zero-copy ring: the small-frame regime must learn, exchange, and
    stay incident-free."""
    run = _run_block("lora", steps=steps)
    task_obj = make_task("lora", seed=seed)
    config = make_local_config(
        n_peers,
        seed=seed,
        base_port=base_port,
        run=run,
        rx_server=rx_server,
        obs=dict(
            incidents=True,
            incident_path=os.path.join(workdir, "incidents-{me}.jsonl"),
        ),
    )
    summary = run_training(config, task_obj, workdir, leg="lora")
    report = build_report(workdir)
    merged = 0
    for node in summary["nodes"]:
        for _, peer in sorted(node["health"]["peers"].items()):
            merged += int(peer.get("successes", 0))
    incidents = sum(
        len(n["incident_clusters"]) for n in report["nodes"].values()
    )
    stt = _median([n["steps_to_target"] for n in summary["nodes"]])
    verdict = {
        "d": task_obj.d,
        "frame_bytes": task_obj.d * 4,
        "steps_to_target": stt,
        "exchanges_succeeded": merged,
        "incident_clusters": incidents,
        "adapter_only_ok": 90_000 <= task_obj.d <= 110_000,
        "converged_ok": stt is not None,
        "exchanged_ok": merged > 0,
        "quiet_incidents_ok": incidents == 0,
    }
    return LegResult(
        leg="lora",
        ok=_verdict_ok(verdict),
        verdict=verdict,
        summary=summary,
        report=report,
        workdir=workdir,
    )
