# PR 35, chip call 2 (one chip), after call 1's lanes: chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr35-call2.sh
# tpch-sf1.q1, the claimed cell: parent (.bench_checkout/: `git archive 9c0f675`) against change, order
# P C C-traced C P, a seed a pair and one for the traced run, untraced at 51 s; then one pair of
# tpcds-sf1-store.q3-q55 (its four Filters feed joins: expected inside its spread) if the call's time allows.
# CHANGE_DIR=$PWD/.smoke_checkout runs the change from a `git archive $(git write-tree)`: the committed files are enough.
PR_TAG=pr35; CALL=${CALL:-call2}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1; S=tpcds-sf1-store.q3-q55
A=${SEED_A:-3500418897}; B=${SEED_B:-3500523633}; T=${SEED_T:-3500628373}; ST=${SEED_S:-3500733081}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $Q1 $A 0; facts parent-$Q1-$A-t0
bench_run change $Q1 $A 0; facts change-$Q1-$A-t0
if [ "$(left)" -gt 400 ]; then
  KEEP_TRACE=1 bench_run change $Q1 $T 1
  TR="$OUT/change-$Q1-$T-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr29_trace.py "$TR" >"$OUT/change-$Q1-$T-t1.programs.txt" 2>&1; head -70 "$OUT/change-$Q1-$T-t1.programs.txt" | cut -c1-220
    python3 benchmarks/calls/pr32_spans.py "$TR" | cut -c1-260; rm -f "$TR"
  fi
fi
if [ "$(left)" -gt 500 ]; then
  bench_run change $Q1 $B 0; facts change-$Q1-$B-t0
  bench_run parent $Q1 $B 0; facts parent-$Q1-$B-t0
fi
if [ "$(left)" -gt ${STAR_NEEDS:-1300} ]; then
  bench_run parent $S $ST 0; facts parent-$S-$ST-t0
  if [ "$(left)" -gt 300 ]; then bench_run change $S $ST 0; facts change-$S-$ST-t0; fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -80
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
