# PR 33, chip call 2 (one chip): chiprun --chips 1 --timeout 3300 -- bash benchmarks/calls/pr33-call2.sh
# After call 1's lanes: tpch-sf1.q1, the claimed one-chip cell, parent (.bench_checkout/: `git archive d4e0a73`)
# against change, order P C C-traced C P, a seed a pair and one for the traced run, untraced at 51 s; then one pair
# of tpcds-sf1-store.q3-q55 (runs the changed stages: expected inside its spread) if the call's time allows (its
# set-up is ~840 s with nothing cached). One compile cache for both sides. Every run starts only if it can end.
PR_TAG=pr33; CALL=call2; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3200} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1; S=tpcds-sf1-store.q3-q55
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $Q1 3300209441 0; facts parent-$Q1-3300209441-t0
bench_run change $Q1 3300209441 0; facts change-$Q1-3300209441-t0
if [ "$(left)" -gt 400 ]; then
  KEEP_TRACE=1 bench_run change $Q1 3300314173 1
  T="$OUT/change-$Q1-3300314173-t1.trace.json"
  if [ -f "$T" ]; then
    python3 benchmarks/calls/pr29_trace.py "$T" jit__body >"$OUT/change-$Q1-3300314173-t1.programs.txt" 2>&1; head -60 "$OUT/change-$Q1-3300314173-t1.programs.txt"
    python3 benchmarks/calls/pr32_spans.py "$T" | cut -c1-260; rm -f "$T"
  fi
fi
if [ "$(left)" -gt 500 ]; then
  bench_run change $Q1 3300418897 0; facts change-$Q1-3300418897-t0
  bench_run parent $Q1 3300418897 0; facts parent-$Q1-3300418897-t0
fi
if [ "$(left)" -gt 1300 ]; then
  bench_run parent $S 3300523633 0; facts parent-$S-3300523633-t0
  if [ "$(left)" -gt 300 ]; then bench_run change $S 3300523633 0; facts change-$S-3300523633-t0; fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call2.jsonl" | cut -c1-300 | head -80
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
