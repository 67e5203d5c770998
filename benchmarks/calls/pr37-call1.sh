# PR 37, chip call 1 (one chip): chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr37-call1.sh
# First the lanes, before any timing of the cell is believed: q1's dense and sorted group-by at full size, lane for
# lane over three seeds, the device's time by program for both forms, and the sweep of the domain that places
# _DENSE_MAX_SLOTS (benchmarks/calls/pr37_dense.py). Only if no lane differs: tpch-sf1.q1, the claimed cell, parent
# (.bench_checkout/: `git archive 19f68cd`) against change, order P C C-traced P-traced C P, a seed a pair and one for
# the traced runs, untraced at 51 s. CHANGE_DIR=$PWD/.smoke_checkout runs the change from a
# `git archive $(git write-tree)`: the committed files are enough.
PR_TAG=pr37; CALL=${CALL:-call1}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1
A=${SEED_A:-3700314173}; B=${SEED_B:-3700418897}; T=${SEED_T:-3700523633}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
if [ -z "$SKIP_LANES" ]; then
  (cd "$CHANGE_DIR" && python3 benchmarks/calls/pr37_dense.py --budget-s ${DENSE_BUDGET_S:-1500} --out "$OUT/dense.jsonl") 2>"$OUT/dense.err" | cut -c1-1800
  rc=${PIPESTATUS[0]}; echo "dense rc $rc"; grep -v "cpu_aot_loader" "$OUT/dense.err" | tail -5 | cut -c1-300
  if [ "$rc" != 0 ]; then exit $rc; fi
fi
traced() {  # side cell seed
  KEEP_TRACE=1 bench_run $1 $2 $3 1
  TR="$OUT/$1-$2-$3-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/$1-$2-$3-t1.attribution.txt" 2>&1
    head -${HEAD:-60} "$OUT/$1-$2-$3-t1.attribution.txt" | cut -c1-200
    python3 benchmarks/calls/pr29_trace.py "$TR" >"$OUT/$1-$2-$3-t1.programs.txt" 2>&1; head -34 "$OUT/$1-$2-$3-t1.programs.txt" | cut -c1-200
    gzip -f "$TR"
  fi
}
bench_run parent $Q1 $A 0; facts parent-$Q1-$A-t0
bench_run change $Q1 $A 0; facts change-$Q1-$A-t0
if [ "$(left)" -gt 300 ]; then traced change $Q1 $T; fi
if [ "$(left)" -gt 300 ] && [ -z "$SKIP_PARENT_TRACE" ]; then HEAD=30 traced parent $Q1 $T; fi
if [ "$(left)" -gt 400 ]; then
  bench_run change $Q1 $B 0; facts change-$Q1-$B-t0
  bench_run parent $Q1 $B 0; facts parent-$Q1-$B-t0
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -60
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
