# Chip call 1, on a host with one TPU, from the root of a checkout (~45 min): bash benchmarks/calls/pr40-call1.sh
# The lanes first, before any timing of the cell is believed (ROADMAP F1, C12: the exact sum is a fenced program):
# (1) q1's ten answer columns from the parent (.bench_checkout/: `git archive 1e7cde0`) and from the change, bit for
# bit, on two seeds (benchmarks/calls/pr40_lanes.py); (2) the one program against the un-jitted chain over f64acc on
# four q1 seeds and q6's two (pr29_exact.py); (3) q1's dense and sorted forms on three seeds, the device's time by
# program, and the domain swept at 6 and 64 groups, the second past f64acc's 16 (pr37_dense.py). Then tpch-sf1.q1,
# the claimed cell, in the order P C C-traced C P, a seed a pair and one for the traced run, untraced at 51 s.
# CHANGE_DIR=$PWD/.smoke_checkout runs the change from a `git archive $(git write-tree)`.
PR_TAG=pr40; CALL=${CALL:-call1}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2500} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1
A=${SEED_A:-4000209441}; B=${SEED_B:-4000314173}; T=${SEED_T:-4000418897}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
if [ -z "$SKIP_LANES" ]; then
  python3 benchmarks/calls/pr40_lanes.py --root "$HERE/.bench_checkout" --save "$OUT/lanes-parent.npz" 2>"$OUT/lanes-parent.err"
  echo "lanes parent rc $?"
  python3 benchmarks/calls/pr40_lanes.py --root "$CHANGE_DIR" --save "$OUT/lanes-change.npz" \
    --against "$OUT/lanes-parent.npz" 2>"$OUT/lanes-change.err"
  rc=$?; echo "lanes change rc $rc"; grep -v "cpu_aot_loader" "$OUT/lanes-change.err" | tail -3 | cut -c1-300
  (cd "$CHANGE_DIR" && python3 benchmarks/calls/pr29_exact.py --out "$OUT/exact.jsonl" \
    --seeds 2900000000,2900104729,2900209458,2200007920) 2>"$OUT/exact.err" | cut -c1-400 | tail -14
  rc2=${PIPESTATUS[0]}; echo "exact rc $rc2"
  (cd "$CHANGE_DIR" && python3 benchmarks/calls/pr37_dense.py --parts lanes,sweep --domains 6,64 --budget-s 900 \
    --out "$OUT/dense.jsonl") 2>"$OUT/dense.err" | cut -c1-1800
  rc3=${PIPESTATUS[0]}; echo "dense rc $rc3"; grep -v "cpu_aot_loader" "$OUT/dense.err" | tail -3 | cut -c1-300
  if [ "$rc" != 0 ] || [ "$rc2" != 0 ] || [ "$rc3" != 0 ]; then exit 1; fi
fi
traced() {  # side cell seed
  KEEP_TRACE=1 bench_run $1 $2 $3 1
  TR="$OUT/$1-$2-$3-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/$1-$2-$3-t1.attribution.txt" 2>&1
    head -40 "$OUT/$1-$2-$3-t1.attribution.txt" | cut -c1-200
    python3 benchmarks/calls/pr29_trace.py "$TR" >"$OUT/$1-$2-$3-t1.programs.txt" 2>&1; head -40 "$OUT/$1-$2-$3-t1.programs.txt" | cut -c1-200
    gzip -f "$TR"
  fi
}
bench_run parent $Q1 $A 0; facts parent-$Q1-$A-t0
bench_run change $Q1 $A 0; facts change-$Q1-$A-t0
if [ "$(left)" -gt 300 ]; then traced change $Q1 $T; fi
if [ "$(left)" -gt 400 ]; then
  bench_run change $Q1 $B 0; facts change-$Q1-$B-t0
  bench_run parent $Q1 $B 0; facts parent-$Q1-$B-t0
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -60
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
