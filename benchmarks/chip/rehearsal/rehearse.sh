#!/bin/sh
# Every CPU rehearsal of the benchmark, from the root of the checkout:
#   sh benchmarks/chip/rehearsal/rehearse.sh
# Each run.py call serves a few seconds of a traffic file end to end on a
# toy model behind the real cell's worker flags and then fails ONLY on the
# platform check (exit 1, "not on a TPU: no result"): that is the pass
# condition. The tp4 cell starts the tensor-parallel worker on four virtual
# CPU devices.
set -u
export JAX_PLATFORMS=cpu
cells=benchmarks/chip/rehearsal/cells.json
fail=0
python3 benchmarks/chip/rehearsal/check_trace.py || fail=1
for cell in tiny-mistral.decode-saturated tiny-qwen.long-prompt tiny-tp4.decode-saturated; do
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
exit $fail
