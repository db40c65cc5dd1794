#!/usr/bin/env python
"""On-chip jax.profiler trace of the ResNet-20 stacked train step.

VERDICT r4 weak #1 asked for profile-level evidence behind the 8.6 % MFU
row.  `experiments/resnet20_roofline.py` supplies the cost-model half
(HBM-bound, ≈ the memory ceiling); this script supplies the measured
half: a real profiler trace of the EXACT
benchmark step (8 peers × b64, bf16, SGD, ring exchange — the
`mfu_accounting.build_resnet20` program), plus a fresh step-time
measurement from the same run, so the roofline's 7.40 ms input and the
trace come from one session.

Writes:
- `artifacts/resnet20_trace/` — the profiler trace (tensorboard-style
  `plugins/profile/...` directory; a few MB),
- `artifacts/resnet20_trace.json` — summary: backend, step_ms, trace
  size, validity.

Refuses to run on a non-chip backend (a CPU trace would say nothing
about where the v5e's step time goes).
"""

from __future__ import annotations

import datetime
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "experiments"))

TRACE_DIR = os.path.join(REPO, "artifacts", "resnet20_trace")
ARTIFACT = os.path.join(REPO, "artifacts", "resnet20_trace.json")
TIMED_STEPS = 50
TRACED_STEPS = 5


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


def main() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"refusing to run: backend is {backend!r}, not the chip "
            "(a CPU trace says nothing about the v5e step)",
            file=sys.stderr,
        )
        raise SystemExit(2)

    from mfu_accounting import build_resnet20

    from dpwa_tpu.utils.profiling import timed_loop

    # NOT re-wrapped in an outer jax.jit: the step is already jitted
    # inside make_stacked_train_step WITH donate_argnums=(0,), and an
    # outer jit would inline the inner one and silently drop the
    # donation — the trace would then profile an allocation pattern the
    # real benchmark step never has.  (mfu_accounting only adds the
    # outer jit to get .lower(); timing/tracing must not.)
    step, (state, batch), info, _ = build_resnet20()

    # Compile + settle outside both the timer and the trace.
    state, losses, _ = step(state, batch)

    t_step, (state, losses) = timed_loop(
        lambda c, k: step(c[0], batch)[:2],
        (state, losses),
        TIMED_STEPS,
    )

    # Fresh dir per run: jax.profiler.trace APPENDS a new
    # plugins/profile/<ts> run, so a retried or prior-round trace would
    # otherwise accumulate and corrupt trace_bytes + the forensics.
    if os.path.isdir(TRACE_DIR):
        import shutil

        shutil.rmtree(TRACE_DIR)
    os.makedirs(TRACE_DIR, exist_ok=True)
    with jax.profiler.trace(TRACE_DIR):
        for _ in range(TRACED_STEPS):
            state, losses, _ = step(state, batch)
        jax.block_until_ready(losses)  # completion inside the trace window

    out = {
        "experiment": "resnet20_trace",
        "backend": backend,
        "device": str(jax.devices()[0].device_kind),
        "config": info,
        "step_ms": round(float(t_step) * 1e3, 3),
        "steps_per_sec": round(1.0 / float(t_step), 1),
        "traced_steps": TRACED_STEPS,
        "trace_dir": os.path.relpath(TRACE_DIR, REPO),
        "trace_bytes": _dir_bytes(TRACE_DIR),
        "captured_at_utc": datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    with open(ARTIFACT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
