# PR 31, chip call 2: chiprun --chips 1 --timeout 1500 -- bash benchmarks/calls/pr31-call2.sh
# The two one-chip cells, which reach no line of parallel/table_ops.py: one pair each, P C, at 51 s. The change is
# .smoke_checkout/ (`git archive $(git write-tree)`), the parent .bench_checkout/ (`git archive ed90296`).
PR_TAG=pr31; CALL=call2; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent rowconv-212x1m.to-rows 3100400033 0
bench_run change rowconv-212x1m.to-rows 3100400033 0
bench_run change tpch-sf1.q1 3100500041 0
bench_run parent tpch-sf1.q1 3100500041 0
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call2.jsonl"
