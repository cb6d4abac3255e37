#!/bin/sh
# One time-to-accuracy row: benchmarks/tta_row.sh <variant>
# Used by tpu_suite.sh: W=1 on the chip JAX selects, full-width model,
# target 0.99, bf16. --dispatch-timeout turns a device that stops
# answering mid-run into a diagnosed abort (the trainer watchdog); the
# outer `timeout` additionally bounds hangs the watchdog cannot see
# (compilation happens before the watchdog arms). A failed row exits
# non-zero. Writes $R/tta_<variant>.json only on success (tmp + move), so
# a failed re-run never clobbers a good row.
set -u
cd "$(dirname "$0")/.."
R=benchmarks/results
mkdir -p "$R"
# The canonical row set — `tta_row.sh --list` prints it so tpu_suite.sh
# does not hardcode the list.
VARIANTS="single sync async sync_sharding async_sharding lm"
if [ "${1:-}" = "--list" ]; then
  echo "$VARIANTS"
  exit 0
fi
v="$1"
timeout "${TTA_ROW_TIMEOUT_S:-2400}" \
  python benchmarks/time_to_accuracy.py --variant "$v" \
  --workers 1 --target 0.99 --max-epochs 20 --bf16 \
  --dispatch-timeout 300 \
  --json "$R/tta_${v}.json.tmp" 2>"$R/tta_${v}.log" || exit $?
mv "$R/tta_${v}.json.tmp" "$R/tta_${v}.json"
