#!/usr/bin/env bash
# 2-process jax.distributed CPU smoke (the CI multihost leg).
#
# CPU only: it forces virtual CPU devices and starts several JAX
# processes. Never run it on a TPU host, where a chip belongs to one
# process (use `python chip_smoke.py --four-chips` there).
#
# Launches NUM_PROCESSES copies of repro.launch.distributed on localhost,
# each with LOCAL_DEVICES virtual CPU devices, sharing one coordinator.
# Each process asserts the global topology (process count/index, local vs
# global device lists, per-process device ownership) and runs process-local
# jitted compute; rank 0 prints "MULTIHOST SMOKE OK". Cross-process XLA
# collectives are NOT exercised — the jax CPU backend implements the
# distributed runtime but not multiprocess computations (see
# src/repro/launch/distributed.py).
#
#   bash scripts/run_multihost.sh            # 2 procs x 2 devices
#   NUM_PROCESSES=2 LOCAL_DEVICES=4 bash scripts/run_multihost.sh
set -euo pipefail
cd "$(dirname "$0")/.."

NUM_PROCESSES="${NUM_PROCESSES:-2}"
LOCAL_DEVICES="${LOCAL_DEVICES:-2}"
PORT="${PORT:-12355}"
COORD="127.0.0.1:${PORT}"
LOGDIR="$(mktemp -d)"
trap 'rm -rf "$LOGDIR"' EXIT

pids=()
for ((i = 0; i < NUM_PROCESSES; i++)); do
  PYTHONPATH=src python -m repro.launch.distributed \
    --coordinator "$COORD" \
    --num-processes "$NUM_PROCESSES" \
    --process-id "$i" \
    --local-devices "$LOCAL_DEVICES" \
    >"$LOGDIR/proc$i.log" 2>&1 &
  pids+=($!)
done

status=0
for ((i = 0; i < NUM_PROCESSES; i++)); do
  wait "${pids[$i]}" || status=$?
done

cat "$LOGDIR"/proc*.log

if [[ $status -ne 0 ]]; then
  echo "FAIL: a process exited non-zero ($status)" >&2
  exit "$status"
fi
grep -q "MULTIHOST SMOKE OK" "$LOGDIR/proc0.log" || {
  echo "FAIL: rank 0 did not report MULTIHOST SMOKE OK" >&2
  exit 1
}
echo "multihost smoke passed (${NUM_PROCESSES} procs x ${LOCAL_DEVICES} devices)"
