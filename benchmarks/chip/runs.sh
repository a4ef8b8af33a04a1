#!/bin/sh
# Several runs of one cell in one call, each run's kept samples copied aside:
#   chiprun --timeout 3000 -- sh benchmarks/chip/runs.sh <workload> <tag> <trace 0|1> <seed> [<seed> ...]
# A run overwrites `.bench_chip/<workload>/client_samples.json`; this copies
# it (and a traced run's `layer_ctx.json`) to `chiprun_out/runs/<tag>/` with
# the run's output, where `tails.py` reads them. The window is BENCHMARK.json's
# `run_seconds`. A builder's tool, from the root of the checkout; no part of
# a benchmark run.
set -u
w=$1; tag=$2; trace=$3; shift 3
out=chiprun_out/runs/$tag
mkdir -p $out
for s in "$@"; do
  t0=$(date +%s)
  python3 benchmarks/chip/run.py --workload $w --seed $s --trace $trace > $out/$s.log 2> $out/$s.err
  rc=$?
  cp .bench_chip/$w/client_samples.json $out/$s.samples.json 2>/dev/null
  [ "$trace" = 1 ] && cp .bench_chip/$w/layer_ctx.json $out/$s.layer_ctx.json 2>/dev/null
  echo "RUN $w seed=$s trace=$trace rc=$rc wall=$(( $(date +%s) - t0 ))s"
  tail -n 1 $out/$s.log
  grep -E "set-up|checkpoint|lateness|window " $out/$s.log
done
