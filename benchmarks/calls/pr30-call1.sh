# PR 30, the only chip call: chiprun --chips 1 --timeout 2700 -- bash benchmarks/calls/pr30-call1.sh
# PR 30 touches tests/, ci/, benchmarks/ and the records only: no file a cell runs. The call shows that the
# benchmark still builds and answers right from the committed files (the change is .smoke_checkout/, a
# `git archive $(git write-tree)` of the final tree; the parent .bench_checkout/, `git archive ae33624`) and that
# nothing moves beyond its bound: rowconv-212x1m.to-rows (the sidecar path the 66 recovered tests cover) P C C P,
# then tpch-sf1.q1 one pair P C. tpcds-sf10-web.q95-x4 is left to the driver.
PR_TAG=pr30; CALL=call1; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent rowconv-212x1m.to-rows 3000000011 0
bench_run change rowconv-212x1m.to-rows 3000000011 0
bench_run change rowconv-212x1m.to-rows 3000104729 0
bench_run parent rowconv-212x1m.to-rows 3000104729 0
bench_run parent tpch-sf1.q1 3000200003 0
bench_run change tpch-sf1.q1 3000200003 0
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call1.jsonl"
