# PR 29, chip call 4: chiprun --chips 1 --timeout 600 -- bash benchmarks/calls/pr29-call4.sh
# rowconv-212x1m.to-rows never enters ops/aggregate.py: one pair P C on one chip, the change from .smoke_checkout/
# (`git archive $(git write-tree)`), to see that nothing moves by more than its bound.
PR_TAG=pr29; CALL=call4; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
bench_run parent rowconv-212x1m.to-rows 2930000023 0
bench_run change rowconv-212x1m.to-rows 2930000023 0
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call4.jsonl"
