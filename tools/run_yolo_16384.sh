#!/bin/bash
# YOLO v3 scaling-curve point at 16384 synthetic images (VERDICT r4 #10,
# deferred from earlier in r5 for chip budget). Same recipe as the 8192
# gate (lr 1e-3, batch 32, flip-augmented synthetic detection set,
# --keep-best) at 2x data; 30 epochs is 2x the images-seen of the 8192
# run's peak epoch (28/50). Supervised-restart loop: the stall watchdog
# exits 75 (EX_TEMPFAIL) on a wedged runtime call and we relaunch into
# the bit-exact --resume path.
set -uo pipefail
cd "$(dirname "$0")/.."
L="logs/gate_yolo_16384-$(date +%Y-%m-%d-%H-%M-%S).log"
mkdir -p logs
WORKDIR=runs/gates16k
RESUME=""
for attempt in $(seq 1 8); do
  echo "[supervisor] attempt $attempt (resume='$RESUME')" | tee -a "$L"
  # --rss-limit-gb: self-preempt + relaunch resets the process RSS
  # long before the box OOMs
  python train.py -m yolov3 --num-classes 5 --lr 1e-3 --batch-size 32 \
    --epochs 30 --synthetic-size 16384 --keep-best \
    --stall-timeout 600 --stall-abort --rss-limit-gb 80 \
    --workdir "$WORKDIR" $RESUME 2>&1 | tee -a "$L"
  code=${PIPESTATUS[0]}
  if [ "$code" -eq 0 ]; then
    break
  elif [ "$code" -eq 75 ] || [ "$code" -eq 143 ]; then
    echo "[supervisor] exit $code -> restart with --resume" | tee -a "$L"
    RESUME="--resume"
  else
    echo "[supervisor] exit $code (non-retryable)" | tee -a "$L"
    exit "$code"
  fi
done
if [ "${code:-1}" -ne 0 ]; then
  echo "[supervisor] giving up: training never completed (last exit $code)" | tee -a "$L"
  exit "$code"
fi
python evaluate.py detection -m yolov3 --num-classes 5 \
  --workdir "$WORKDIR/yolov3" 2>&1 | tee -a "$L"
