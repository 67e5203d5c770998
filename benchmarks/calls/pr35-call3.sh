# PR 35, chip call 3 (four chips): chiprun --chips 4 --timeout 2400 -- bash benchmarks/calls/pr35-call3.sh
# tpcds-sf10-web.q95-x4, unclaimed and expected not to move: its local tier's dimension filters feed joins (counted
# `plan.filter.compacted`), its mesh Filter keeps `present`, its last aggregate groups 704 rows with no Filter under
# it (a `count_all` by start differences now). One pair, parent (.bench_checkout/: `git archive 9c0f675`) then change
# (.smoke_checkout/: `git archive $(git write-tree)`), one seed, 51 s; the second run only if it can end.
PR_TAG=pr35; CALL=call3; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup\|^fact" "$OUT/$1.out" "$OUT/$1.err" | cut -c1-400 | tail -4; }
CELL=tpcds-sf10-web.q95-x4; SEED=${SEED:-3500837803}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $CELL $SEED 0; facts parent-$CELL-$SEED-t0
if [ "$(left)" -gt 900 ]; then bench_run change $CELL $SEED 0; facts change-$CELL-$SEED-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call3.jsonl" | cut -c1-400 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
