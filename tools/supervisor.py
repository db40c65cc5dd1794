#!/usr/bin/env python
"""Restart supervisor: keep a fleet of gossip workers alive.

The paper's deployment story is peer-to-peer — there is no parameter
server whose job description includes "restart the dead" — so that job
lands here: a small, stdlib-only process supervisor that

- spawns each worker as a subprocess (through
  :func:`dpwa_tpu.utils.launch.child_process_env`, so a parent's frozen
  ``XLA_FLAGS``/``JAX_PLATFORMS`` never leak into a child's backend
  init);
- watches for exits, and optionally polls each worker's ``/healthz``
  endpoint (``health.healthz_port`` in the YAML config) to catch the
  hung-but-alive case a waitpid can't see;
- restarts crashed workers with capped exponential backoff, setting
  ``DPWA_BOOTSTRAP=1`` in the child environment so the replacement
  rejoins by fetching a healthy donor's full state over the TCP STATE
  wire (see :mod:`dpwa_tpu.recovery` and docs/recovery.md) instead of
  cold-starting — zero shared disk;
- gives up on a worker after ``max_restarts`` consecutive failures
  (a worker that crashes on every boot is a bug, not a blip) while
  leaving the rest of the fleet running.

Importable (:class:`Supervisor` drives the chaos-soak test) and
runnable::

    $ python tools/supervisor.py --n 4 -- \
          python my_worker.py --config cfg.yaml --peer {i}

``{i}`` / ``{name}`` in the command template expand per worker.  The
survivors' pairing schedule is untouched by any of this: restarts only
re-enter a peer through the scoreboard's probation/probe path, and the
rejoiner lands on the donor's step so the deterministic draws agree.
"""

from __future__ import annotations

import argparse
import dataclasses
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Sequence

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from any cwd
    sys.path.insert(0, _REPO_ROOT)

from dpwa_tpu.utils.launch import child_process_env  # noqa: E402


@dataclasses.dataclass
class WorkerSpec:
    """One supervised worker.

    ``argv`` is the exec vector.  ``env`` is merged over the sanitized
    base environment (and over it, the supervisor's own
    ``DPWA_BOOTSTRAP`` flag on restarts).  ``healthz_port`` enables the
    liveness poll against ``http://127.0.0.1:<port>/healthz``."""

    name: str
    argv: List[str]
    env: Optional[Dict[str, str]] = None
    healthz_port: Optional[int] = None
    cwd: Optional[str] = None


@dataclasses.dataclass
class _WorkerState:
    spec: WorkerSpec
    proc: Optional[subprocess.Popen] = None
    started_at: float = 0.0
    restarts: int = 0
    healthz_strikes: int = 0
    gave_up: bool = False
    restart_due: Optional[float] = None  # backoff deadline (monotonic)
    last_exit: Optional[int] = None


class Supervisor:
    """Spawn, watch, and restart a fleet of :class:`WorkerSpec` s."""

    def __init__(
        self,
        workers: Sequence[WorkerSpec],
        *,
        repo_root: Optional[str] = _REPO_ROOT,
        platform: Optional[str] = "cpu",
        max_restarts: int = 5,
        backoff_base_s: float = 0.5,
        backoff_max_s: float = 30.0,
        healthz_timeout_s: float = 1.0,
        healthz_grace_s: float = 10.0,
        healthz_strikes: int = 3,
        poll_interval_s: float = 0.25,
        bootstrap_on_restart: bool = True,
        on_event: Optional[Callable[[Dict[str, Any]], None]] = None,
    ):
        self._workers = [_WorkerState(spec=w) for w in workers]
        self._base_env = child_process_env(repo_root, platform=platform)
        self.max_restarts = int(max_restarts)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.healthz_timeout_s = float(healthz_timeout_s)
        self.healthz_grace_s = float(healthz_grace_s)
        self.healthz_strikes = int(healthz_strikes)
        self.poll_interval_s = float(poll_interval_s)
        self.bootstrap_on_restart = bootstrap_on_restart
        self.events: List[Dict[str, Any]] = []
        self._on_event = on_event

    # ------------------------------------------------------------------

    def _event(self, kind: str, worker: _WorkerState, **fields: Any) -> None:
        rec = {"event": kind, "worker": worker.spec.name, **fields}
        self.events.append(rec)
        if self._on_event is not None:
            self._on_event(rec)

    def _spawn(self, w: _WorkerState, *, bootstrap: bool) -> None:
        env = dict(self._base_env)
        if w.spec.env:
            env.update(w.spec.env)
        if bootstrap:
            # The replacement must rejoin with a peer's state, not a
            # cold init — the whole point of the STATE wire.
            env["DPWA_BOOTSTRAP"] = "1"
        w.proc = subprocess.Popen(w.spec.argv, env=env, cwd=w.spec.cwd)
        w.started_at = time.monotonic()
        w.healthz_strikes = 0
        w.restart_due = None
        self._event(
            "spawn", w, pid=w.proc.pid, bootstrap=bootstrap,
            restarts=w.restarts,
        )

    def start(self) -> None:
        for w in self._workers:
            self._spawn(w, bootstrap=False)

    def _healthz_ok(self, w: _WorkerState) -> Optional[bool]:
        """True/False from the endpoint; None when not applicable yet."""
        port = w.spec.healthz_port
        if port is None:
            return None
        if time.monotonic() - w.started_at < self.healthz_grace_s:
            return None  # still booting: jax init can dwarf any timeout
        url = f"http://127.0.0.1:{port}/healthz"
        try:
            with urllib.request.urlopen(
                url, timeout=self.healthz_timeout_s
            ) as resp:
                return 200 <= resp.status < 300
        except (urllib.error.URLError, OSError, TimeoutError):
            return False

    def _schedule_restart(self, w: _WorkerState, reason: str) -> None:
        w.proc = None
        if w.restarts >= self.max_restarts:
            w.gave_up = True
            self._event("gave_up", w, reason=reason, restarts=w.restarts)
            return
        delay = min(
            self.backoff_max_s, self.backoff_base_s * (2.0 ** w.restarts)
        )
        w.restarts += 1
        w.restart_due = time.monotonic() + delay
        self._event(
            "restart_scheduled", w, reason=reason, delay_s=round(delay, 3),
            restarts=w.restarts,
        )

    def poll(self) -> Dict[str, Any]:
        """One supervision pass; returns a status summary."""
        now = time.monotonic()
        for w in self._workers:
            if w.gave_up:
                continue
            if w.proc is None:
                if w.restart_due is not None and now >= w.restart_due:
                    self._spawn(w, bootstrap=self.bootstrap_on_restart)
                continue
            code = w.proc.poll()
            if code is not None:
                w.last_exit = code
                if code == 0:
                    # Clean exit is completion, not a crash.
                    w.proc = None
                    self._event("exited", w, code=0)
                    continue
                self._event("crashed", w, code=code)
                self._schedule_restart(w, reason=f"exit:{code}")
                continue
            ok = self._healthz_ok(w)
            if ok is False:
                w.healthz_strikes += 1
                if w.healthz_strikes >= self.healthz_strikes:
                    self._event(
                        "unhealthy", w, strikes=w.healthz_strikes
                    )
                    self._kill(w)
                    self._schedule_restart(w, reason="healthz")
            elif ok is True:
                w.healthz_strikes = 0
        return self.status()

    def status(self) -> Dict[str, Any]:
        running = sum(
            1 for w in self._workers if w.proc is not None
            and w.proc.poll() is None
        )
        return {
            "running": running,
            "pending_restart": sum(
                1 for w in self._workers
                if w.proc is None and w.restart_due is not None
                and not w.gave_up
            ),
            "gave_up": sum(1 for w in self._workers if w.gave_up),
            "done": sum(
                1 for w in self._workers
                if w.proc is None and w.restart_due is None
                and not w.gave_up
            ),
            "restarts": {w.spec.name: w.restarts for w in self._workers},
        }

    def all_done(self) -> bool:
        s = self.status()
        return s["running"] == 0 and s["pending_restart"] == 0

    def run(
        self,
        timeout_s: Optional[float] = None,
        until: Optional[Callable[["Supervisor"], bool]] = None,
    ) -> Dict[str, Any]:
        """Supervise until every worker is done/given-up, ``until(self)``
        goes true, or ``timeout_s`` elapses.  Always reaps the fleet on
        the way out."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        try:
            while True:
                self.poll()
                if self.all_done():
                    break
                if until is not None and until(self):
                    break
                if deadline is not None and time.monotonic() >= deadline:
                    self._event_all("timeout")
                    break
                time.sleep(self.poll_interval_s)
        finally:
            self.stop()
        return self.status()

    def _event_all(self, kind: str) -> None:
        for w in self._workers:
            if w.proc is not None and w.proc.poll() is None:
                self._event(kind, w)

    def _kill(self, w: _WorkerState, grace_s: float = 3.0) -> None:
        if w.proc is None:
            return
        if w.proc.poll() is None:
            w.proc.terminate()
            try:
                w.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()
        w.last_exit = w.proc.returncode
        w.proc = None

    def stop(self) -> None:
        """Terminate every live worker (SIGTERM, then SIGKILL)."""
        for w in self._workers:
            self._kill(w)

    # Mapping of worker name -> live pid (tests kill a victim directly).
    def pids(self) -> Dict[str, Optional[int]]:
        return {
            w.spec.name: (
                w.proc.pid
                if w.proc is not None and w.proc.poll() is None
                else None
            )
            for w in self._workers
        }


def _expand(template: Sequence[str], i: int, name: str) -> List[str]:
    return [a.format(i=i, name=name) for a in template]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--n", type=int, default=1, help="number of workers")
    ap.add_argument(
        "--name-fmt", default="worker{i}",
        help="worker name template ({i} expands)",
    )
    ap.add_argument(
        "--healthz-base-port", type=int, default=None,
        help="poll /healthz on base+i per worker (matches a config whose "
        "peers set health.healthz_port accordingly)",
    )
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--backoff-base", type=float, default=0.5)
    ap.add_argument("--backoff-max", type=float, default=30.0)
    ap.add_argument(
        "--duration", type=float, default=None,
        help="stop after this many seconds (default: until all exit)",
    )
    ap.add_argument(
        "--no-bootstrap", action="store_true",
        help="restart cold instead of setting DPWA_BOOTSTRAP=1",
    )
    ap.add_argument(
        "cmd", nargs=argparse.REMAINDER,
        help="worker command template after '--'; {i}/{name} expand",
    )
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        ap.error("missing worker command (after '--')")
    workers = []
    for i in range(args.n):
        name = args.name_fmt.format(i=i)
        workers.append(
            WorkerSpec(
                name=name,
                argv=_expand(cmd, i, name),
                healthz_port=(
                    None
                    if args.healthz_base_port is None
                    else args.healthz_base_port + i
                ),
            )
        )
    sup = Supervisor(
        workers,
        max_restarts=args.max_restarts,
        backoff_base_s=args.backoff_base,
        backoff_max_s=args.backoff_max,
        bootstrap_on_restart=not args.no_bootstrap,
        on_event=lambda rec: print(f"[supervisor] {rec}", flush=True),
    )
    signal.signal(signal.SIGTERM, lambda *_: sup.stop() or sys.exit(143))
    sup.start()
    final = sup.run(timeout_s=args.duration)
    print(f"[supervisor] final: {final}", flush=True)
    return 0 if final["gave_up"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
