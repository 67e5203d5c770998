# PR 33, chip calls 1 and 2 in one (no chip was free for half an hour: one wait, not two):
# chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr33-call12.sh
# The lanes first (pr33-call1.sh); the timing (pr33-call2.sh) only if no lane differs.
bash benchmarks/calls/pr33-call1.sh
if grep -q '"comparisons_that_differ": 0' chiprun_out/pr33/bits-1chip.jsonl; then
  CALL_SECONDS=2500 bash benchmarks/calls/pr33-call2.sh
else
  echo "lanes differ: no timing"
fi
