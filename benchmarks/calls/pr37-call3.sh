# PR 37, chip call 3 (four chips): chiprun --chips 4 --timeout 2400 -- bash benchmarks/calls/pr37-call3.sh
# tpcds-sf10-web.q95-x4, unclaimed and expected within ±5 ms of 1,966: after exchange.gather its `per_order`
# group-by (704 rows keyed by ws_order_number) is probed, finds a domain far past the bound and sorts as before (one
# small program and one round trip more), and its last `keys=()` aggregate is dense with a domain of 1 (a sort of
# 704 zeros and its gathers less). Both run on four-device arrays: the new programs must take them. One pair, parent
# (.bench_checkout/: `git archive 19f68cd`) then change (CHANGE_DIR), one seed, 51 s; the second run only if it can end.
PR_TAG=pr37; CALL=call3; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup\|^fact" "$OUT/$1.out" "$OUT/$1.err" | cut -c1-400 | tail -4; }
CELL=tpcds-sf10-web.q95-x4; SEED=${SEED:-3700942561}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $CELL $SEED 0; facts parent-$CELL-$SEED-t0
if [ "$(left)" -gt 900 ]; then bench_run change $CELL $SEED 0; facts change-$CELL-$SEED-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call3.jsonl" | cut -c1-400 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
