#!/bin/sh
# Every CPU rehearsal of the benchmark, from the root of the checkout:
#   sh benchmarks/chip/rehearsal/rehearse.sh
# Found by name, so a family brings its own as new files: every
# rehearsal/check_*.py is run, and every cell of every rehearsal/cells*.json
# (a list may hold only `configs` and `workloads`; the metrics are
# BENCHMARK.json's). Each run.py call serves a few seconds of a traffic file
# end to end on a toy model behind a real cell's worker flags, compares the
# served tokens with the family's float32 reference, and then fails ONLY on
# the platform check (exit 1, "not on a TPU: no result"): that is the pass
# condition. The tp4 cell starts the tensor-parallel worker on four virtual
# CPU devices.
set -u
export JAX_PLATFORMS=cpu
dir=benchmarks/chip/rehearsal
fail=0
for check in $dir/check_*.py; do
  if python3 $check; then echo "ok   $check"; else echo "FAIL $check"; fail=1; fi
done
for cells in $dir/cells*.json; do
  for cell in $(python3 -c "import json, sys; print(' '.join(w['name'] for w in json.load(open(sys.argv[1]))['workloads']))" $cells); do
    for trace in 0 1; do
      out=$(python3 benchmarks/chip/run.py --bench-file $cells --workload $cell --seed 2147483999 --seconds 6 --trace $trace 2>&1)
      rc=$?
      if [ $rc -eq 1 ] && echo "$out" | grep -q "correct: True" \
          && echo "$out" | tail -1 | grep -q "not on a TPU: no result"; then
        echo "ok   $cell trace=$trace"
      else
        echo "FAIL $cell trace=$trace (rc=$rc)"; echo "$out" | tail -5; fail=1
      fi
    done
  done
done
exit $fail
