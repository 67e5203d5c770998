# PR 29, chip call 3: chiprun --chips 4 --timeout 1440 -- bash benchmarks/calls/pr29-call3.sh
# tpcds-sf10-web.q95-x4 on four chips: the parent (.bench_checkout/, `git archive 35be169`) and the change
# (.smoke_checkout/, `git archive $(git write-tree)` of the final tree: the committed files are enough), order P C
# [C P] [C traced], a seed a pair, untraced at 51 s. A run holds the four chips ~2.6 min warm; with nothing cached the
# first set-up is ~970 s, so every further run starts only if it can end inside the call's 24 minutes.
PR_TAG=pr29; CALL=call3; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 1440 - ( $(date +%s) - t0 ) )); }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent tpcds-sf10-web.q95-x4 2920000021 0
grep -h "^setup\|^fact" "$OUT/parent-tpcds-sf10-web.q95-x4-2920000021-t0.out" "$OUT/parent-tpcds-sf10-web.q95-x4-2920000021-t0.err" | cut -c1-300 | tail -8
bench_run change tpcds-sf10-web.q95-x4 2920000021 0
grep -h "^setup\|^fact" "$OUT/change-tpcds-sf10-web.q95-x4-2920000021-t0.out" "$OUT/change-tpcds-sf10-web.q95-x4-2920000021-t0.err" | cut -c1-300 | tail -8
if [ "$(left)" -gt 440 ]; then
  bench_run change tpcds-sf10-web.q95-x4 2920104750 0
  bench_run parent tpcds-sf10-web.q95-x4 2920104750 0
fi
if [ "$(left)" -gt 230 ]; then
  bench_run change tpcds-sf10-web.q95-x4 2920209479 1
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call3.jsonl"
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
