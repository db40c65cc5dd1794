#!/usr/bin/env python
"""Re-measure every training benchmark's steps/s on the live chip.

The MFU table (BASELINE.md, ``artifacts/mfu_accounting.json``) pairs
XLA-counted FLOPs/step with chip-measured steps/s.  The steps/s column
dates from round 2 (before PR 1), and the BERT row runs f32, which
understates MFU against the bf16-peak denominator.  This script refreshes
all of it in one pass on a chip host:

- reruns each benchmark example CLI at the EXACT config the baseline
  table cites (so the numbers stay comparable round over round),
- adds the bf16 BERT config (the honest-denominator row the round-3
  VERDICT asked the MFU table to gain),
- parses the shared ``steps/sec (... on <plat> xN): <val>`` line each
  example prints, refusing results measured on a non-chip backend,
- writes ``artifacts/train_steps_refresh.json``.

MFU re-pairing is then arithmetic:
``python experiments/mfu_accounting.py --configs <name> --steps-per-sec
<name>=<val>`` (FLOPs/step do not change between rounds).

The parent never touches JAX, so each example child has the chip to
itself.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "artifacts", "train_steps_refresh.json")

# name -> example argv at the BASELINE.md table's exact configs.  Steps
# are kept short: compile dominates wall time and the examples already
# exclude it from the timed window.
CONFIGS = {
    "resnet20_cifar10": [
        "examples/cifar10/main.py", "--transport", "stacked",
        "--synthetic", "--bf16", "--steps", "200",
    ],
    "resnet50_imagenet": [
        "examples/imagenet/main.py", "--transport", "stacked",
        "--peers", "8", "--batch-size", "8", "--bf16",
        "--steps", "60",
    ],
    "bert_base_mlm": [
        "examples/bert/main.py", "--transport", "stacked",
        "--peers", "4", "--group-size", "2", "--batch-size", "4",
        "--steps", "40",
    ],
    "bert_base_mlm_bf16": [
        "examples/bert/main.py", "--transport", "stacked",
        "--peers", "4", "--group-size", "2", "--batch-size", "4",
        "--bf16", "--steps", "60",
    ],
    "llama_lora_tiny": [
        "examples/llama_lora/main.py", "--transport", "stacked",
        "--peers", "8", "--steps", "100",
    ],
}

STEPS_RE = re.compile(
    r"steps/sec \(all \d+ peers, incl\. exchange, on (\w+) x\d+\):\s*"
    r"([0-9.]+)"
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_one(name: str, argv: list[str], timeout_s: float) -> dict:
    cmd = [sys.executable] + argv
    log(f"[{name}] {' '.join(argv)}")
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=REPO, env=os.environ.copy(),
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"timeout after {timeout_s:.0f}s"}
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-2:]
        return {"ok": False, "error": f"rc={proc.returncode}: {' | '.join(tail)}"}
    m = STEPS_RE.search(proc.stdout)
    if not m:
        return {"ok": False, "error": "no steps/sec line in output"}
    plat, val = m.group(1), float(m.group(2))
    if plat != "tpu":
        # A CPU run must never refresh a chip table.
        return {"ok": False, "error": f"measured on {plat!r}, not the chip"}
    log(f"[{name}] {val} steps/s on {plat}")
    return {
        "ok": True,
        "steps_per_sec": val,
        "platform": plat,
        "cmd": " ".join(argv),
    }


def _load_artifact() -> dict:
    if os.path.exists(ARTIFACT):
        try:
            with open(ARTIFACT) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    return {
        "experiment": "train_steps_refresh",
        "note": (
            "steps/s re-measured at the BASELINE.md table's exact "
            "configs; bert_base_mlm_bf16 is the bf16-denominator row "
            "the MFU table gains this round; each row carries its own "
            "measured_at_utc (rows are written as they land, so a "
            "killed run keeps completed measurements)"
        ),
        "configs": {},
    }


def _write_artifact(out: dict) -> None:
    with open(ARTIFACT + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(ARTIFACT + ".tmp", ARTIFACT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*", default=list(CONFIGS),
                    choices=list(CONFIGS))
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-example watchdog (compile + timed steps)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure rows that already landed ok")
    args = ap.parse_args()

    # Resumable by construction: rows that already measured ok are kept,
    # and each fresh row is committed to disk the moment it lands — an
    # outer watchdog killing this process can cost at most the in-flight
    # config.  Each row carries its own
    # measured_at_utc; there is deliberately no file-level timestamp,
    # which would re-stamp old rows on a partial rerun.
    out = _load_artifact()
    for name in args.configs:
        prev = out["configs"].get(name)
        if prev and prev.get("ok") and not args.force:
            log(f"[{name}] already measured ok "
                f"({prev.get('measured_at_utc', '?')}); skipping")
            continue
        rec = run_one(name, CONFIGS[name], args.timeout)
        rec["measured_at_utc"] = datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%SZ")
        out["configs"][name] = rec
        _write_artifact(out)

    ok = bool(out["configs"]) and all(
        out["configs"].get(n, {}).get("ok") for n in args.configs
    )
    _write_artifact(out)
    print(json.dumps(out, indent=1))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
