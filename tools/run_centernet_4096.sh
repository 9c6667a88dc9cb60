#!/bin/bash
# CenterNet scaling-curve point at 4096 synthetic images (extends the
# 1024 -> 2048 generalization curve). Same two-phase recipe as
# `make gate_centernet` (50 epochs, then +15 at the CenterNet-paper x10
# lr drop via --resume) at 2x data. Supervised restarts: stall watchdog
# exits 75 on a wedged runtime call, --rss-limit-gb self-preempts
# (exit 143) ahead of an OOM kill; both relaunch into the bit-exact
# --resume path.
set -uo pipefail
cd "$(dirname "$0")/.."
L="logs/gate_centernet_4096-$(date +%Y-%m-%d-%H-%M-%S).log"
mkdir -p logs
WORKDIR=runs/gates4k

run_phase() {  # run_phase <epochs> <extra flags...>
  local epochs=$1; shift
  local resume=""
  for attempt in $(seq 1 8); do
    echo "[supervisor] phase to epoch $epochs attempt $attempt (resume='$resume')" | tee -a "$L"
    python train.py -m centernet --num-classes 5 --epochs "$epochs" \
      --synthetic-size 4096 --keep-best --stall-timeout 420 --stall-abort \
      --rss-limit-gb 80 --workdir "$WORKDIR" "$@" $resume 2>&1 | tee -a "$L"
    code=${PIPESTATUS[0]}
    if [ "$code" -eq 0 ]; then
      return 0
    elif [ "$code" -eq 75 ] || [ "$code" -eq 143 ]; then
      echo "[supervisor] exit $code -> restart with --resume" | tee -a "$L"
      resume="--resume"
    else
      echo "[supervisor] exit $code (non-retryable)" | tee -a "$L"
      return "$code"
    fi
  done
  echo "[supervisor] giving up (last exit $code)" | tee -a "$L"
  return "$code"
}

run_phase 50 || exit
run_phase 65 --lr 1e-4 --resume || exit
python evaluate.py detection -m centernet --num-classes 5 --size 128 \
  --workdir "$WORKDIR/centernet" 2>&1 | tee -a "$L"
