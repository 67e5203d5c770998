# PR 37, chip call 4 (one chip), after the review: chiprun --chips 1 --timeout 1800 -- bash benchmarks/calls/pr37-call4.sh
# First what the probe costs a group-by it refuses (benchmarks/calls/pr37_dense.py --parts probe: one INT32 key
# over 1 << 15 values at 6,001,215 and at 65,536 rows, the sort path behind the probe against the parent's path with
# the dtype gate shut, and the probe alone). Then tpch-sf1.q1 on the tree as it is handed in (the aggregates take
# one record of either form's groups; the programs are the ones calls 1 and 2 measured): parent (.bench_checkout/:
# `git archive 19f68cd`), change, change traced, a seed a pair. CHANGE_DIR=$PWD/.smoke_checkout runs the change from
# a `git archive $(git write-tree)`: the committed files are enough.
PR_TAG=pr37; CALL=${CALL:-call4}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-1650} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1
A=${SEED_A:-3701047289}; T=${SEED_T:-3701152003}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
(cd "$CHANGE_DIR" && python3 benchmarks/calls/pr37_dense.py --parts probe --reps 5 --out "$OUT/probe.jsonl") 2>"$OUT/probe.err" | cut -c1-1800
rc=${PIPESTATUS[0]}; echo "probe rc $rc"; grep -v "cpu_aot_loader" "$OUT/probe.err" | tail -5 | cut -c1-300
bench_run parent $Q1 $A 0; facts parent-$Q1-$A-t0
bench_run change $Q1 $A 0; facts change-$Q1-$A-t0
if [ "$(left)" -gt 300 ]; then
  KEEP_TRACE=1 bench_run change $Q1 $T 1
  TR="$OUT/change-$Q1-$T-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$Q1-$T-t1.attribution.txt" 2>&1
    head -40 "$OUT/change-$Q1-$T-t1.attribution.txt" | cut -c1-200; rm -f "$TR"
  fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
