#!/usr/bin/env bash
# The reference's "cluster": N local processes, one per YAML node, distinct
# --name, shared config (SURVEY.md §3.4).  TCP doesn't care that they share
# a machine.  They do care about the accelerator: a chip belongs to one
# process, so the workers are pinned to the CPU.
set -euo pipefail
cd "$(dirname "$0")"
STEPS="${STEPS:-200}"
pids=()
for name in node0 node1; do
  python main.py --transport tcp --name "$name" --config nodes.yaml \
    --platform cpu --steps "$STEPS" "$@" &
  pids+=($!)
done
for pid in "${pids[@]}"; do wait "$pid"; done
