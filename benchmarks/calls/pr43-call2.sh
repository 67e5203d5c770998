# Chip call 2, on a host with one TPU, from the root of a checkout (~17 min): bash benchmarks/calls/pr43-call2.sh
# The store star, the one other cell whose requests run sort_by_key (two Sorts of 650 and 816 rows, a STRING column
# gathered after each): tpcds-sf1-store.q3-q55 in the order P C C P, a seed a pair, untraced at 51 s (the parent
# first and cold: both sides share one compile cache), then one traced run of the change. The change from
# .smoke_checkout/ (a `git archive $(git write-tree)`), the parent from .bench_checkout/.
PR_TAG=pr43; CALL=${CALL:-call2}; CHANGE_DIR=${CHANGE_DIR:-$PWD/.smoke_checkout}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-1300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
ST=tpcds-sf1-store.q3-q55; A=4300756733; B=4300861469
bench_run parent $ST $A 0; facts parent-$ST-$A-t0
if [ "$(left)" -gt 400 ]; then bench_run change $ST $A 0; facts change-$ST-$A-t0; fi
if [ "$(left)" -gt 400 ]; then
  KEEP_TRACE=1 bench_run change $ST 4300966201 1
  TR="$OUT/change-$ST-4300966201-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$ST-t1.attribution.txt" 2>&1
    head -30 "$OUT/change-$ST-t1.attribution.txt" | cut -c1-200; gzip -f "$TR"
  fi
fi
if [ "$(left)" -gt 250 ]; then bench_run change $ST $B 0; facts change-$ST-$B-t0; fi
if [ "$(left)" -gt 200 ]; then bench_run parent $ST $B 0; facts parent-$ST-$B-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -40
