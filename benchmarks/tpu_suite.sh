#!/bin/sh
# One-shot TPU measurement suite: run everything BASELINE.md records from
# the real chip, writing JSON into benchmarks/results/. Every tool imports
# JAX, requires a TPU, runs and fails loudly; the first tool that fails
# fails the suite (set -e) — nothing is skipped, retried or carried past.
# Each tool writes to a temp file moved into place only on success, so a
# failed re-run never clobbers a good result. One process holds the chip
# at a time: the tools run one after another, never alongside each other.
#
#   sh benchmarks/tpu_suite.sh
#
# Rows produced:
#   bench_tpu.json          headline sweep + sync W=1 (bench.py)
#   lm_tpu.json             long-context LM tokens/s + MFU, xla vs flash
#   step_anatomy_tpu.json   per-piece fixed-cost attribution incl. the
#                           tail-matmul conv lowering head-to-head
#   bench_tpu_tailmm.json   the headline sweep re-run with
#                           BENCH_CONV_MATMUL=tail (comparison record)
#   ring_balance_tpu.json   zigzag vs contiguous causal critical path
#                           (1-chip device-role emulation, real kernels)
#   adam_kernel_tpu.json    fused Pallas Adam vs XLA-fused chain
#   tta_<variant>.json      time-to-target-accuracy, W=1 product trainers
set -ex
cd "$(dirname "$0")/.."
R=benchmarks/results
mkdir -p "$R"

python bench.py >"$R/bench_tpu.json.tmp" 2>"$R/bench_tpu.log"
mv "$R/bench_tpu.json.tmp" "$R/bench_tpu.json"

run() { # run <name> <cmd...>   (cmd must accept --json <path>)
  name=$1; shift
  "$@" --json "$R/$name.json.tmp" >"$R/$name.log" 2>&1
  mv "$R/$name.json.tmp" "$R/$name.json"
}

# Long-context LM set: tokens/s + MFU over seq 512-4096, xla einsum vs
# the Pallas flash kernel.
run lm_tpu python benchmarks/lm_bench.py

# Conv lowering head-to-head on the chip: the full product step with the
# tail convs as matmuls vs the conv kernels, plus the per-piece
# attribution of the fixed term.
run step_anatomy_tpu python benchmarks/step_anatomy.py

# The headline sweep is ALSO recorded with the tail convs as matmuls, so
# the conv-lowering comparison exists at every batch size (bench_tpu.json
# stays the product-default record; compare the two files offline).
# bench.py prints its JSON line to stdout (no --json flag).
BENCH_CONV_MATMUL=tail python bench.py \
  >"$R/bench_tpu_tailmm.json.tmp" 2>"$R/bench_tpu_tailmm.log"
mv "$R/bench_tpu_tailmm.json.tmp" "$R/bench_tpu_tailmm.json"

# Zigzag-vs-contiguous causal critical path with real kernels (1-chip
# device-role emulation; see ring_balance.py).
run ring_balance_tpu python benchmarks/ring_balance.py

run adam_kernel_tpu python benchmarks/adam_kernel.py

# Every variant family on the real chip (W=1): the sharded rows fold their
# shards onto the one device — degenerate as parallelism but they execute
# the REAL sharded programs on TPU. Row config (timeouts, target, dtype)
# AND the variant list live in tta_row.sh. The list goes through an
# assignment so a failing `--list` stops the suite under set -e.
TTA_VARIANTS=$(sh benchmarks/tta_row.sh --list)
for v in $TTA_VARIANTS; do
  sh benchmarks/tta_row.sh "$v"
done
